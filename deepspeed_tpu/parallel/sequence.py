"""Sequence/context parallelism: Ulysses all-to-all + ring attention.

TPU-native re-design of the reference's DeepSpeed-Ulysses
(``deepspeed/sequence/layer.py`` — ``single_all_to_all`` :41,
``DistributedAttention.forward`` :181: scatter heads / gather sequence
before local attention, inverse after) plus **ring attention**, the
context-parallel mechanism the reference lacks (SURVEY §5.7: "ring
attention / blockwise: not present"), which on TPU rides ICI neighbor
links via ``lax.ppermute``.

Both are drop-in ``attention_fn`` implementations for
``deepspeed_tpu.models`` (signature ``(q, k, v, mask=None, scale=None)``),
wrapping the local computation in a nested ``shard_map`` over the ``seq``
mesh axis so they compose with jit/SPMD and TP head sharding.

Constraints (same as the reference, layer.py:52): Ulysses needs
``num_heads % (seq * tensor) == 0`` and ``num_kv_heads % seq == 0``;
ring attention only needs the sequence divisible by the axis size.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..comm.mesh import BATCH_AXES, MeshTopology, SEQ_AXIS, TENSOR_AXIS
from ..models.layers import causal_attention


def make_ulysses_local(base_attention: Callable = causal_attention
                       ) -> Callable:
    """Per-shard Ulysses attention for callers ALREADY inside a shard_map
    over ``seq`` (e.g. the pipeline loss, which runs one outer shard_map
    over pipe x data x seq).  Same a2a dance as ``make_ulysses_attention``
    without the nested shard_map."""

    def attn(q, k, v, mask=None, scale=None):
        a2a = functools.partial(lax.all_to_all, axis_name=SEQ_AXIS,
                                split_axis=2, concat_axis=1, tiled=True)
        q_, k_, v_ = a2a(q), a2a(k), a2a(v)
        if mask is not None:
            mask = lax.all_gather(mask, SEQ_AXIS, axis=1, tiled=True)
        o = base_attention(q_, k_, v_, mask=mask, scale=scale)
        return lax.all_to_all(o, axis_name=SEQ_AXIS, split_axis=1,
                              concat_axis=2, tiled=True)

    return attn


def make_ulysses_attention(topology: MeshTopology,
                           base_attention: Callable = causal_attention
                           ) -> Callable:
    """All-to-all attention: inputs arrive sequence-sharded; a2a trades the
    sequence split for a head split, local attention sees the full sequence
    for its head subset, inverse a2a restores sequence sharding."""
    mesh = topology.mesh
    sp = topology.sp_size
    if sp == 1:
        return base_attention

    def attn(q, k, v, mask=None, scale=None):
        H, Hkv = q.shape[2], k.shape[2]
        tp = topology.tp_size
        if (H % (sp * tp)) or (Hkv % (sp * tp)):
            raise ValueError(
                f"Ulysses needs heads divisible by seq*tensor axes: "
                f"H={H}, Hkv={Hkv}, seq={sp}, tensor={tp}")

        # heads-scatter/seq-gather before local attention, inverse after
        # (reference single_all_to_all layer.py:41)
        inner = make_ulysses_local(base_attention)

        def local(q, k, v, mask):
            return inner(q, k, v, mask=mask, scale=scale)

        qspec = P(BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)
        mspec = P(BATCH_AXES, SEQ_AXIS) if mask is not None else P()
        return shard_map(
            local, mesh=mesh,
            in_specs=(qspec, qspec, qspec, mspec),
            out_specs=qspec,
            check_vma=False)(q, k, v, mask)

    return attn


# --------------------------------------------------------------------------
# Ring attention (context parallelism over ICI neighbor links)
# --------------------------------------------------------------------------

def _block_attn_update(q, k, v, o, m, l, row0, col0, causal, scale,
                       slopes=None, kv_mask=None):
    """Flash-style streaming-softmax update for one KV block.

    q [B,s,H,D] holds global rows [row0, row0+s); k/v [B,s,Hkv,D] global
    cols [col0, col0+s).  o/m/l are the running output, row-max and
    row-sum (fp32).  ``slopes``: optional ALiBi per-local-head slopes
    [Hkv, rep] — the bias is slope * GLOBAL key position, which the ring
    formulation has by construction (col0).  Returns updated (o, m, l).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k) * scale
    logits = logits.astype(jnp.float32)
    cols = col0 + jnp.arange(k.shape[1])
    if slopes is not None:
        logits = logits + (slopes[None, :, :, None, None]
                           * cols[None, None, None, None, :]
                           .astype(jnp.float32))
    if causal:
        rows = row0 + jnp.arange(S)
        keep = rows[:, None] >= cols[None, :]
        logits = jnp.where(keep[None, None, None], logits, -1e30)
    if kv_mask is not None:                     # [B, s] padding mask of
        logits = jnp.where(                     # the block we hold now
            kv_mask[:, None, None, None, :].astype(bool), logits, -1e30)

    blk_max = logits.max(axis=-1)                        # [B,Hkv,rep,q]
    new_m = jnp.maximum(m, blk_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m[..., None])               # [B,Hkv,rep,q,k]
    new_l = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhrqk,bkhd->bhrqd", p.astype(q.dtype), v)
    new_o = o * correction[..., None] + pv.astype(jnp.float32)
    return new_o, new_m, new_l


def make_ring_attention(topology: MeshTopology, causal: bool = True,
                        alibi_heads: int = 0,
                        attn_scale=None) -> Callable:
    """Blockwise ring attention: Q stays put, KV blocks rotate around the
    ``seq`` axis via ``ppermute`` while a streaming softmax accumulates —
    O(S/sp) memory per device, neighbor-only ICI traffic, arbitrary
    sequence lengths (the >1M-token regime Ulysses alone cannot reach
    because its head split caps sp at num_heads).  ``alibi_heads``: the
    global head count of an ALiBi model — the bias (slope * global key
    position) folds into each block update; heads stay unsplit on the
    seq axis here, but a tensor head split slices the slope series."""
    mesh = topology.mesh
    sp = topology.sp_size
    if sp == 1:
        if alibi_heads:
            from ..models.layers import make_alibi_attention
            return make_alibi_attention()
        return causal_attention
    default_scale = attn_scale

    def attn(q, k, v, mask=None, scale=None):
        scale_ = scale if scale is not None else default_scale
        scale_ = scale_ if scale_ is not None \
            else 1.0 / math.sqrt(q.shape[-1])
        have_mask = mask is not None

        def local(q, k, v, *mk):
            mask = mk[0] if mk else None
            B, s, H, D = q.shape
            Hkv = k.shape[2]
            idx = lax.axis_index(SEQ_AXIS)
            row0 = idx * s

            slopes = None
            if alibi_heads:
                from ..models.layers import alibi_slopes
                sl = alibi_slopes(alibi_heads)
                if H != alibi_heads:   # tensor axis split the heads
                    off = lax.axis_index(TENSOR_AXIS) * H
                    sl = lax.dynamic_slice_in_dim(sl, off, H)
                slopes = sl.reshape(Hkv, H // Hkv)

            o = jnp.zeros((B, Hkv, H // Hkv, s, D), jnp.float32)
            m = jnp.full((B, Hkv, H // Hkv, s), -jnp.inf, jnp.float32)
            l = jnp.zeros((B, Hkv, H // Hkv, s), jnp.float32)
            perm = [(i, (i + 1) % sp) for i in range(sp)]

            def body(i, carry):
                # the padding mask (when present) rotates with its KV
                # block; without one the carry omits it entirely — no
                # dead ppermute on the common unmasked path (have_mask
                # is a trace-time constant)
                o, m, l, k, v = carry[:5]
                km = carry[5] if have_mask else None
                src = (idx - i) % sp          # global block we hold now
                o, m, l = _block_attn_update(
                    q, k, v, o, m, l, row0, src * s, causal, scale_,
                    slopes=slopes, kv_mask=km)
                k = lax.ppermute(k, SEQ_AXIS, perm)
                v = lax.ppermute(v, SEQ_AXIS, perm)
                nxt = (o, m, l, k, v)
                if have_mask:
                    nxt = nxt + (lax.ppermute(km, SEQ_AXIS, perm),)
                return nxt

            init = (o, m, l, k, v) + ((mask,) if have_mask else ())
            o, m, l = lax.fori_loop(0, sp, body, init)[:3]
            out = o / jnp.maximum(l, 1e-30)[..., None]
            # [B,Hkv,rep,s,D] -> [B,s,H,D]
            out = out.transpose(0, 3, 1, 2, 4).reshape(B, s, H, D)
            return out.astype(q.dtype)

        qspec = P(BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)
        in_specs = [qspec, qspec, qspec]
        operands = [q, k, v]
        if have_mask:
            in_specs.append(P(BATCH_AXES, SEQ_AXIS))
            operands.append(mask)
        return shard_map(local, mesh=mesh,
                         in_specs=tuple(in_specs),
                         out_specs=qspec,
                         check_vma=False)(*operands)

    return attn


def make_ulysses_alibi_base(num_heads: int, sp: int, tp: int = 1,
                            attn_scale=None) -> Callable:
    """ALiBi base attention for INSIDE a Ulysses ``shard_map``: after
    the head-scatter a2a each rank owns a contiguous slice of the global
    head set, so the slopes must be the matching slice of the global
    geometric series — offset = tensor_block + seq_sub_block.
    ``attn_scale``: a custom softmax scale (cfg.attn_scale) — rebuilt
    here because this path bypasses the model's resolved wrapper."""
    from ..models import layers as L

    h_tp = num_heads // tp
    h_local = h_tp // sp

    def head_offset():
        off = lax.axis_index(SEQ_AXIS) * h_local
        if tp > 1:
            off = off + lax.axis_index(TENSOR_AXIS) * h_tp
        return off

    base = None
    if attn_scale is not None:
        def base(q, k, v, mask=None, **kw):
            return causal_attention(q, k, v, mask=mask, scale=attn_scale,
                                    **kw)

    return L.make_alibi_attention(base, head_offset=head_offset,
                                  total_heads=num_heads)


def make_attention(topology: MeshTopology, mode: str = "ulysses",
                   base_attention: Callable = causal_attention,
                   alibi_heads: int = 0, alibi_scale=None) -> Callable:
    """(reference config: sequence_parallel.mode).  ``alibi_heads``:
    global head count of an ALiBi model — Ulysses builds the
    head-offset-aware bias inside its shard_map; ring folds
    slope * global-key-position into each block update."""
    if topology.sp_size == 1:
        return base_attention
    if mode == "ulysses":
        if alibi_heads:
            base_attention = make_ulysses_alibi_base(
                alibi_heads, topology.sp_size, topology.tp_size,
                attn_scale=alibi_scale)
        return make_ulysses_attention(topology, base_attention)
    if mode == "ring":
        return make_ring_attention(topology, alibi_heads=alibi_heads,
                                   attn_scale=alibi_scale)
    raise ValueError(f"Unknown sequence-parallel mode {mode!r}")


def sp_cross_entropy(logits, labels, topology: MeshTopology, mask=None):
    """SP-aware LM loss (reference: sequence/cross_entropy.py:11 —
    vocab-parallel loss).  Under SPMD jit the plain fp32 softmax xent is
    already correct for sequence-sharded logits; this alias documents the
    parity point."""
    from ..models.transformer import cross_entropy_loss

    return cross_entropy_loss(logits, labels, mask)
