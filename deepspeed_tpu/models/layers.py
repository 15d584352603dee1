"""Functional layer library with logical-axis parameter metadata.

Every constructor returns ``(params, axes)`` where ``axes`` is a matching
pytree of logical-axis tuples consumed by
:mod:`deepspeed_tpu.parallel.sharding`.  Apply functions are pure.

This replaces the reference's module-injection machinery: where DeepSpeed
walks an existing torch module tree and slices weights imperatively
(``module_inject/auto_tp.py:189``, ``module_inject/layers.py:78-124``
LinearAllreduce/LinearLayer), TPU-native models are *born* with sharding
metadata and XLA places the collectives.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def dense_init(key, in_dim: int, out_dim: int, in_axis: str, out_axis: str,
               bias: bool = True, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    p = {"kernel": jax.random.normal(key, (in_dim, out_dim)) * scale}
    a = {"kernel": (in_axis, out_axis)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,))
        a["bias"] = (out_axis,)
    return p, a


def dense(p, x):
    y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def embedding_init(key, vocab: int, dim: int, scale: float = 0.02):
    return ({"table": jax.random.normal(key, (vocab, dim)) * scale},
            {"table": ("vocab", "embed")})


def embed(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def layernorm_init(dim: int):
    return ({"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
            {"scale": ("norm",), "bias": ("norm",)})


def layernorm(p, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rmsnorm_init(dim: int):
    return ({"scale": jnp.ones((dim,))}, {"scale": ("norm",)})


def rmsnorm(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


def qk_rmsnorm(scale, x, eps: float):
    """QK-norm of OLMoE / OLMo-2: ONE RMSNorm over a token's whole q (or
    k) projection, all heads together, with a learned scale, before the
    head split's rotary.  x: [..., heads, head_dim]; scale: [heads,
    head_dim] (the flat published vector, viewed by head)."""
    x32 = x.astype(jnp.float32)
    ms = (x32 * x32).mean(axis=(-2, -1), keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings (reference kernel analog:
# csrc/transformer/inference apply_rotary_pos_emb, v2 kv_rotary)
# --------------------------------------------------------------------------

def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (reference consumers: the bloom injection
    policy, module_inject/containers/bloom.py; math from the ALiBi
    paper): geometric sequence from 2^(-8/n), closest power of two
    padded like the HF implementation for non-power-of-two head counts."""
    n = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
    slopes = [base ** (i + 1) for i in range(n)]
    if n < num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * n) - 3)))
        slopes += [extra_base ** (2 * i + 1)
                   for i in range(num_heads - n)]
    return jnp.asarray(slopes, jnp.float32)


def make_alibi_attention(base=None, head_offset=None,
                         total_heads: Optional[int] = None):
    """Wrap an attention fn with the ALiBi bias.  Uses the key-position
    form ``slope_h * j`` (the query-position term is constant per softmax
    row and cancels) — exactly HF Bloom's ``build_alibi_tensor``.

    Under manual head sharding (Ulysses inside ``shard_map``) the local
    head block is a SLICE of the global geometric slope series:
    ``total_heads`` fixes the global head count and ``head_offset`` (a
    zero-arg callable, e.g. ``lambda: axis_index(seq) * H_local``)
    locates this shard's first head.  Default: local heads ARE the
    global heads."""
    base_fn = base or causal_attention

    def attn(q, k, v, mask=None, **kw):
        Hl, Sk = q.shape[2], k.shape[1]
        slopes = alibi_slopes(total_heads or Hl)
        if head_offset is not None:
            slopes = jax.lax.dynamic_slice_in_dim(
                slopes, head_offset(), Hl)
        bias = slopes[:, None, None] \
            * jnp.arange(Sk, dtype=jnp.float32)[None, None, :]
        return base_fn(q, k, v, mask=mask, bias=bias, **kw)
    return attn


class Yarn(NamedTuple):
    """YaRN's scaling of the rotary embedding, a config's
    ``rope_scaling`` block of ``type`` ``yarn`` (DeepSeek-V2): a model
    trained over ``original`` positions served over ``factor`` times as
    many.  The frequencies that turn more than ``beta_fast`` times in
    the original context stay, those that turn fewer than ``beta_slow``
    times are divided by ``factor``, and the ones between are blended by
    a linear ramp over the pair's index.  ``m(t) = 0.1 t ln(factor) +
    1``: cos and sin are multiplied by ``m(mscale) / m(mscale_all_dim)``
    and the softmax scale of the attention by ``m(mscale_all_dim)^2``
    (``score_scale``; a ``mscale_all_dim`` of 0 multiplies nothing)."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _m(self, t: float) -> float:
        return 0.1 * t * math.log(self.factor) + 1.0 \
            if self.factor > 1 else 1.0

    @property
    def table_scale(self) -> float:
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def score_scale(self) -> float:
        return self._m(self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0

    def ramp_ends(self, dim: int, theta: float):
        """(low, high): the pairs' indices between which the ramp
        rises from 0 (the pair keeps its frequency) to 1 (divided by
        ``factor``)."""
        def turns_at(r):        # the index of the pair that turns r times
            return dim * math.log(self.original / (2 * math.pi * r)) \
                / (2 * math.log(theta))
        return (max(math.floor(turns_at(self.beta_fast)), 0),
                min(math.ceil(turns_at(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, theta: float):
        f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        low, high = self.ramp_ends(dim, theta)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        return f / self.factor * ramp + f * (1.0 - ramp)


def rope_freqs(head_dim: int, max_seq: int, theta: float = 10000.0,
               yarn: Optional[Yarn] = None):
    """(cos, sin) ``[max_seq, head_dim / 2]``; with ``yarn`` at its
    blended frequencies and times its table's multiplier."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
    else:
        inv = yarn.inv_freq(head_dim, theta)
    t = jnp.arange(max_seq, dtype=jnp.float32)
    ang = jnp.outer(t, inv)                    # [S, D/2]
    if yarn is not None and yarn.table_scale != 1.0:
        return jnp.cos(ang) * yarn.table_scale, jnp.sin(ang) * yarn.table_scale
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, positions=None):
    """x: [B, S, H, D]; cos/sin: [maxS, R/2] with R <= D (partial rotary
    — phi-style — rotates only the first R head dims); positions: [B, S]
    or None."""
    if positions is None:
        c = cos[: x.shape[1]][None, :, None, :]
        s = sin[: x.shape[1]][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    rot = 2 * cos.shape[-1]
    xr, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------------------
# Attention (XLA path; Pallas flash kernel plugs in via the same signature)
# --------------------------------------------------------------------------

def causal_attention(q, k, v, mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None, causal: bool = True,
                     bias: Optional[jnp.ndarray] = None,
                     window: Optional[int] = None):
    """q: [B, S, H, D]; k/v: [B, Sk, Hkv, D].  GQA via grouped einsum — KV
    are never materialized at full head count, preserving the memory GQA
    exists to save.  Softmax in fp32 for stability; XLA fuses the block
    onto the MXU.  ``causal=False`` gives bidirectional attention.
    ``bias``: additive attention bias [H, S|1, Sk] (ALiBi et al.).
    ``window``: a query sees only its last ``window`` keys, its own
    among them (a mask over the full score matrix; serving skips the
    blocks, ops/paged_attention.py)."""
    B, S, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32).reshape(
            Hkv, rep, bias.shape[-2], Sk)[None]
    if causal:
        keep = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        if window is not None:
            keep &= ~jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S - window)
        logits = jnp.where(keep[None, None, None], logits, -1e30)
    if mask is not None:                        # [B, Sk] padding mask
        logits = jnp.where(mask[:, None, None, None, :].astype(bool),
                           logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(B, S, H, D)


ACTIVATIONS = {
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
}
