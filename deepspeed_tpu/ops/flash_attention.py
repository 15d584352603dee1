"""Flash attention — Pallas TPU kernels.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/softmax.cu`` + ``attention_softmax_context`` family,
the triton alternates in ``deepspeed/ops/transformer/inference/triton/``,
and the training-side fused softmax of ``csrc/transformer``).

Blockwise streaming-softmax attention (Flash-Attention-2 style) with the
KV stream expressed THROUGH THE GRID: the kv-block index is the
innermost grid dimension, so Mosaic double-buffers one [BK, D] K and V
tile at a time into VMEM while (m, l, acc) persist in VMEM scratch
across the sequential grid steps.  Nothing is ever wholly pinned —
VMEM holds O(BQ·D + BK·D) regardless of S, so the kernel runs at 32k+
context where the earlier whole-KV-resident variant fell back to XLA.

- forward: grid (B, H, Sq/BQ, S/BK); fp32 accumulation, bf16 MXU
  matmuls; per-row LSE saved for the backward.
- backward: recomputation-based two-pass — a dq kernel on the same grid,
  and a dkv kernel on grid (B, Hkv, S/BK, rep·Sq/BQ) streaming the GQA
  query-head group's q/do blocks while dk/dv accumulate in scratch,
  with delta = rowsum(dO·O) precomputed.

Causal skipping: fully-masked block pairs skip their compute via
``pl.when`` (their DMA still runs — grids are static); the diagonal
applies the triangular mask.

Speed against the XLA flash-style path (ops/xla_attention.py): see
PERF.md — ``chip_smoke.py`` times both on the chip at GPT-2-small and
llama3-8b widths and checks the numerics agree to bf16 tolerance.

Falls back to the XLA softmax-attention path for padding masks, ragged
block sizes, or non-TPU backends (interpret mode covers CPU tests).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import causal_attention

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_j_last(i, block_q: int, block_k: int, n_k: int):
    """Last kv-block index (inclusive) visible to q block ``i``."""
    return jnp.minimum(
        jax.lax.div((i + 1) * block_q - 1, block_k), n_k - 1)


def _causal_mask(s, i, j, block_q: int, block_k: int):
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows >= cols, s, NEG_INF)


# ==========================================================================
# forward
# ==========================================================================

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                block_q: int, block_k: int, n_k: int,
                scale: float, causal: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    j_last = _causal_j_last(i, block_q, block_k, n_k) if causal \
        else n_k - 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= j_last)
    def _compute():
        q = q_ref[0, 0]                                    # [BQ, D] bf16
        k = k_ref[0, 0]                                    # [BK, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [BQ, BK]
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BQ, D]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == j_last)
    def _emit():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # slim [BQ, 1] column (trailing singleton keeps the block
        # tile-legal for Mosaic at 1/128th of a lane broadcast)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(
            jnp.maximum(l_ref[:, :1], 1e-30))


def _fwd(q, k, v, scale: float, causal: bool,
         block_q: int, block_k: int):
    """q: [B,H,S,D]; k/v: [B,Hkv,S,D] → (o [B,H,S,D], lse [B,H,S,1])."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    n_k = S // block_k
    grid = (B, H, S // block_q, n_k)

    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, i, j: (b, h // rep, j, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          n_k=n_k, scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            kv_spec, kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_use_interpret(),
        name="flash_attention_fwd",
    )(q, k, v)
    return out[0], out[1]


# ==========================================================================
# backward
# ==========================================================================

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, block_q: int, block_k: int, n_k: int,
               scale: float, causal: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    j_last = _causal_j_last(i, block_q, block_k, n_k) if causal \
        else n_k - 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= j_last)
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                # [BQ, 1]
        delta = delta_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == j_last)
    def _emit():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                block_q: int, block_k: int, n_q: int,
                scale: float, causal: bool, rep: int):
    j = pl.program_id(2)
    t = pl.program_id(3)                 # flat (r, i) stream
    i = jax.lax.rem(t, n_q)
    n_t = rep * n_q

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: q blocks strictly above this kv block contribute nothing
    active = jnp.logical_or(
        jnp.logical_not(causal),
        (i + 1) * block_q - 1 >= j * block_k)

    @pl.when(active)
    def _compute():
        k = k_ref[0, 0]                                    # [BK, D]
        v = v_ref[0, 0]
        q = q_ref[0, 0, 0]                                 # [BQ, D]
        do = do_ref[0, 0, 0]
        lse = lse_ref[0, 0, 0]                             # [BQ, 1]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [BQ, BK]
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _emit():
        # s = scale·qkᵀ ⇒ dk = scale·dsᵀq (q enters the matmul unscaled)
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, scale: float, causal: bool,
         block_q: int, block_k: int):
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    n_q = S // block_q
    n_k = S // block_k
    delta = (do.astype(jnp.float32)
             * o.astype(jnp.float32)).sum(-1, keepdims=True)

    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, i, j: (b, h // rep, j, 0),
                           memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          n_k=n_k, scale=scale, causal=causal),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, vec_spec, vec_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)[0]

    # dkv: kv block owns the scratch; the GQA group's (r, i) q blocks
    # stream through the innermost grid dim
    qg = q.reshape(B, Hkv, rep, S, D)
    dog = do.reshape(B, Hkv, rep, S, D)
    lseg = lse.reshape(B, Hkv, rep, S, 1)
    deltag = delta.reshape(B, Hkv, rep, S, 1)

    def qg_index(b, h, j, t):
        return (b, h, t // n_q, t % n_q, 0)

    kv_blk_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, j, t: (b, h, j, 0),
                               memory_space=pltpu.VMEM)
    qg_spec = pl.BlockSpec((1, 1, 1, block_q, D), qg_index,
                           memory_space=pltpu.VMEM)
    vg_spec = pl.BlockSpec((1, 1, 1, block_q, 1), qg_index,
                           memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                          n_q=n_q, scale=scale, causal=causal, rep=rep),
        grid=(B, Hkv, n_k, rep * n_q),
        in_specs=[qg_spec, kv_blk_spec, kv_blk_spec, qg_spec, vg_spec,
                  vg_spec],
        out_specs=[kv_blk_spec, kv_blk_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_attention_bwd_dkv",
    )(qg, k, v, dog, lseg, deltag)
    return dq, dk, dv


# ==========================================================================
# public API (custom VJP)
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, mask: Optional[jnp.ndarray] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    causal: bool = True):
    """Drop-in ``attention_fn`` ([B, S, H, D] layout, GQA k/v allowed).

    KV streams through the grid, so VMEM use is O(block) and independent
    of S — no sequence-length cap.  Falls back to the XLA path when a
    padding mask is supplied or the sequence doesn't tile evenly (the
    reference keeps an unfused python softmax path the same way)."""
    B, S, H, D = q.shape
    bq, bk = min(block_q, S), min(block_k, S)
    # cross-length attention (Sk != Sq, e.g. diffusers cross-attn) stays
    # on the XLA path: the kernels assume one shared S
    if (mask is not None or k.shape[1] != S or S % bq or S % bk
            or (H % k.shape[2])):
        return causal_attention(q, k, v, mask=mask, scale=scale,
                                causal=causal)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qt = q.transpose(0, 2, 1, 3)                   # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash(qt, kt, vt, float(scale), causal, bq, bk)
    # named so the 'flash' remat policy saves it: flash's custom VJP already
    # recomputes attention internally — replaying the forward kernel under
    # jax.checkpoint would recompute it twice
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_out")
    return o.transpose(0, 2, 1, 3)
