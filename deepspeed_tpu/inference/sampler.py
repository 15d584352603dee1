"""Token samplers (greedy / temperature / top-k / top-p).

The reference delegates sampling to MII / HF ``generate``; a serving
engine needs one in-repo, so this is a small jit-safe sampler family.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => disabled
    top_p: float = 1.0                # 1.0 => disabled
    max_new_tokens: int = 64
    stop_token: Optional[int] = None

    @property
    def sampler_key(self) -> tuple:
        """The fields that change the compiled sampling computation —
        ``stop_token``/``max_new_tokens`` are host-side loop concerns, so
        the jitted step that bakes the sampler in caches executables on
        this key, not the full params."""
        return (self.temperature, self.top_k, self.top_p)

    @property
    def needs_rng(self) -> bool:
        return self.temperature > 0.0


def sample(logits: jnp.ndarray, params: SamplingParams,
           rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """logits [S, V] → token ids [S]."""
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        raise ValueError("temperature sampling requires an rng key "
                         "(the engine supplies one automatically)")
    logits = logits / params.temperature
    if params.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -params.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; keep at least 1
        cutoff_idx = jnp.sum(cum < params.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def row_keys(rng: jax.Array, uids: jnp.ndarray,
             context_lens: jnp.ndarray) -> jnp.ndarray:
    """[max_seqs] per-row sampling keys: ``fold_in(fold_in(rng, uid),
    position)`` where position is the sampled token's index in its
    sequence (= context length after the step).

    This makes a sequence's sampled-token randomness a pure function of
    (base key, uid, position) — invariant to HOW the serving loop
    scheduled the work.  That is what keeps seeded sampling
    token-for-token identical whether a step ran ahead or strict, and
    across prefix-cache hits/misses (a cache hit collapses prefill
    steps, so any per-step key stream would diverge)."""
    def one(u, c):
        return jax.random.fold_in(jax.random.fold_in(rng, u), c)
    return jax.vmap(one)(uids, context_lens)


def window_keys(rng: jax.Array, uids: jnp.ndarray,
                positions: jnp.ndarray) -> jnp.ndarray:
    """[S, W] per-(row, position) sampling keys for a speculative
    verify window: ``fold_in(fold_in(rng, uid), position)`` where
    ``positions[s, j]`` is the post-token position of window column
    ``j`` (the sampled token's index in its sequence).

    EXACTLY the fold :func:`row_keys` applies to a single sampled
    token, evaluated at every drafted position — so the token a verify
    column samples is bit-identical to what the non-speculative path
    would have sampled at the same (uid, position).  That identity is
    the whole parity argument for speculative decoding: acceptance
    compares drafts against the very stream a draft-less engine would
    emit (docs/SERVING.md "Speculative decoding")."""
    def one_row(u, ps):
        row_key = jax.random.fold_in(rng, u)
        return jax.vmap(lambda p: jax.random.fold_in(row_key, p))(ps)
    return jax.vmap(one_row)(uids, positions)


def sample_rows(logits: jnp.ndarray, params: SamplingParams,
                keys: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """logits [S, V] + per-row keys [S, key] → token ids [S].

    The per-row-keyed sibling of :func:`sample` the serving steps bake
    in; greedy ignores ``keys`` entirely (XLA dead-code-eliminates the
    key computation, so the seeded machinery costs nothing at
    temperature 0)."""
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if keys is None:
        raise ValueError("temperature sampling requires per-row keys "
                         "(the engine supplies them automatically)")
    return jax.vmap(lambda l, k: sample(l[None], params, k)[0])(logits, keys)
