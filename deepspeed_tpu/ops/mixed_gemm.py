"""Mixed-input GEMM: int8 weights x bf16 activations, dequant in VMEM.

TPU-native analog of the reference's mixed-input serving GEMMs
(``inference/v2/kernels/core_ops/cuda_linear/include/
weight_prepacking.cuh`` + ``fp6_linear.cu`` — FP6xFP16 GEMM that
dequantizes weight fragments in registers between the global-memory load
and the tensor-core MMA, so the weight read is quantized-sized).  Here
the quantized weight tile is DMA'd into VMEM int8-sized and widened to
bf16 *inside the kernel* right before the MXU dot — HBM traffic for the
weight is 1 byte/element instead of 2 (bf16) or 4 (the dequant-then-
matmul fallback when XLA fails to fuse).

Consumes the row-wise serving layout directly
(:func:`deepspeed_tpu.ops.quant.quantize_rowwise`: int8 payload in the
weight's own shape, fp32 scale per contraction row) — no repacking.

Like the flash kernel (ops/flash_attention.py), this is interpret-tested
on the CPU and compile-tested for a described v5e
(tests/test_tpu_compile.py).  The serving engine runs it where
``InferenceConfig.mixed_gemm="on"`` asks (default "off"; chip_smoke.py
times it against the XLA path, PERF.md has what the chip said).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_bf16(w, interpret: bool):
    """Force the dequantized tile to MATERIALIZE as bf16.

    Interpret mode runs the kernel body as ordinary traced XLA ops, and
    XLA fuses the bf16 dequant multiply straight into the f32 dot —
    skipping the bf16 rounding the MXU feed applies on hardware.  An
    optimization barrier pins the intermediate, so interpret-tested
    numerics match the real kernel (and the bf16 XLA reference paths
    the engine probes against).  No-op on real TPUs."""
    return jax.lax.optimization_barrier(w) if interpret else w


def _mixed_kernel(x_ref, d_ref, s_ref, o_ref, acc_ref, *, interpret):
    """One (bm, bn) output tile; grid dim 2 walks the K blocks."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant IN VMEM: int8 tile -> bf16, scaled per contraction row.
    # bf16 keeps the MXU on its native input width; the f32 accumulator
    # carries the precision.
    w = _round_bf16(d_ref[...].astype(jnp.bfloat16)
                    * s_ref[...].astype(jnp.bfloat16), interpret)
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.bfloat16), w,
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile_plan(x, Kb: int, N: int, block_m: int, block_n: int,
               block_k: int):
    """Shared tiling scaffold for the mixed-GEMM kernels: auto block_m
    (decode steps are small — pad M up to a lane-friendly multiple),
    clamp K/N blocks, and reject non-dividing contractions rather than
    silently pad them.  ``Kb``: the kernel's K-walk extent (K for int8,
    K/2 packed rows for int4).  Returns (x_padded, M, Mp, block_m, bk,
    bn)."""
    M = x.shape[0]
    if block_m <= 0:
        block_m = min(128, max(8, 1 << (max(M - 1, 1)).bit_length()))
    bk = min(block_k, Kb)
    bn = min(block_n, N)
    if Kb % bk or N % bn:
        raise ValueError(f"K-extent={Kb}/N={N} must divide "
                         f"block_k={bk}/block_n={bn}")
    Mp = -(-M // block_m) * block_m
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    return x, M, Mp, block_m, bk, bn


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret",
                                             "out_dtype"))
def mixed_matmul_2d(x: jax.Array, data: jax.Array, scale: jax.Array,
                    *, block_m: int = 0, block_n: int = 512,
                    block_k: int = 512, out_dtype=jnp.bfloat16,
                    interpret: bool = False) -> jax.Array:
    """``x [M, K] @ (int8 data [K, N] * scale [K, 1]) -> [M, N]``."""
    M, K = x.shape
    K2, N = data.shape
    assert K == K2 and scale.shape[0] == K, (x.shape, data.shape,
                                             scale.shape)
    x, M, Mp, block_m, bk, bn = _tile_plan(x, K, N, block_m, block_n,
                                           block_k)
    scale2 = scale.reshape(K, 1)

    out = pl.pallas_call(
        functools.partial(_mixed_kernel, interpret=interpret),
        grid=(Mp // block_m, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
        interpret=interpret,
    )(x, data, scale2)
    return out[:M] if Mp != M else out


def _mixed4_kernel(x1_ref, x2_ref, d_ref, s1_ref, s2_ref, o_ref, acc_ref,
                   *, interpret):
    """Packed-int4 tile: the byte block unpacks IN VMEM into the two
    strided contraction halves (lo nibble = flat row j, hi = j + K/2 —
    ops/quant.quantize_rowwise4), each fed to its own MXU dot against
    the matching activation tile.  HBM streams 0.5 byte/weight."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    from .quant import unpack_nibbles
    lo, hi = unpack_nibbles(d_ref[...])
    w1 = _round_bf16(lo.astype(jnp.bfloat16)
                     * s1_ref[...].astype(jnp.bfloat16), interpret)
    w2 = _round_bf16(hi.astype(jnp.bfloat16)
                     * s2_ref[...].astype(jnp.bfloat16), interpret)
    acc_ref[...] += jax.lax.dot(
        x1_ref[...].astype(jnp.bfloat16), w1,
        preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot(
        x2_ref[...].astype(jnp.bfloat16), w2,
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret",
                                             "out_dtype"))
def mixed4_matmul_2d(x: jax.Array, data: jax.Array, scale: jax.Array,
                     *, block_m: int = 0, block_n: int = 512,
                     block_k: int = 512, out_dtype=jnp.bfloat16,
                     interpret: bool = False) -> jax.Array:
    """``x [M, K] @ unpack(int4 data [K/2, N], scale [K, 1]) -> [M, N]``.

    ``data`` byte row j packs flat contraction rows j (lo nibble) and
    j + K/2 (hi).  The x and scale operands are passed TWICE with offset
    index maps — one view per half — so the kernel needs no gather."""
    M, K = x.shape
    Kh, N = data.shape
    assert K == 2 * Kh and scale.shape[0] == K, (x.shape, data.shape,
                                                 scale.shape)
    x, M, Mp, block_m, bk, bn = _tile_plan(x, Kh, N, block_m, block_n,
                                           block_k)
    nk = Kh // bk
    scale2 = scale.reshape(K, 1)

    out = pl.pallas_call(
        functools.partial(_mixed4_kernel, interpret=interpret),
        grid=(Mp // block_m, N // bn, nk),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, bk),
                         lambda i, j, k, _nk=nk: (i, k + _nk)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bk, 1), lambda i, j, k, _nk=nk: (k + _nk, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
        interpret=interpret,
    )(x, x, data, scale2, scale2)
    return out[:M] if Mp != M else out


def mixed_matmul(x: jax.Array, qt, *, contract_dims: int = 1,
                 interpret: bool = False, out_dtype=None) -> jax.Array:
    """``x @ dequant(qt)`` through the mixed-input kernel family.

    ``x``: [..., K]; ``qt``: a row-wise int8 (weight-shaped payload) or
    packed row-wise int4 ("rowwise4" flat [K/2, N])
    :class:`~deepspeed_tpu.ops.quant.QuantizedTensor` whose payload's
    first ``contract_dims`` dims flatten into the contraction (K) and
    the rest into N — e.g. an attention output projection [H, Dh, d]
    uses ``contract_dims=2``.  Scales on a coarser leading granularity
    than K (per-head for [H, Dh, d]) broadcast down to rows.
    """
    from .quant import is_rowwise_int4
    int4 = is_rowwise_int4(qt)
    assert int4 or (qt.bits == 8 and qt.zero is None), \
        "mixed_matmul consumes the row-wise int8/int4 symmetric layouts"
    if jax.default_backend() != "tpu":
        interpret = True        # CPU/virtual meshes: no Mosaic lowering
    wshape = tuple(qt.shape)
    K = int(np.prod(wshape[:contract_dims]))
    N = int(np.prod(wshape[contract_dims:]))
    lead = x.shape[:-1]
    M = int(np.prod(lead)) if lead else 1
    assert x.shape[-1] == K, (x.shape, wshape, contract_dims)
    s = qt.scale.reshape(-1)
    if s.size != K:
        assert K % s.size == 0, (qt.scale.shape, K)
        # leading-dim scales are constant over their trailing rows
        s = jnp.broadcast_to(s[:, None], (s.size, K // s.size))
    out_dtype = out_dtype or x.dtype
    if int4:
        # the flat packing fixed K at quantize time; a caller using a
        # different contraction split would reshape "successfully" into
        # garbage — reject loudly instead
        assert qt.data.shape[-2] == K // 2, \
            ("rowwise4 payload packed for a different contraction split",
             qt.data.shape, K)
        y = mixed4_matmul_2d(x.reshape(M, K), qt.data.reshape(K // 2, N),
                             s.reshape(K, 1), out_dtype=out_dtype,
                             interpret=interpret)
    else:
        y = mixed_matmul_2d(x.reshape(M, K), qt.data.reshape(K, N),
                            s.reshape(K, 1), out_dtype=out_dtype,
                            interpret=interpret)
    return y.reshape(*lead, *wshape[contract_dims:])


def dequant_matmul_reference(x: jax.Array, qt, out_dtype=None) -> jax.Array:
    """The XLA path this kernel is compared with: bf16 fused
    dequantize (ops/quant.dequantize row-wise fast path) then matmul."""
    from .quant import dequantize
    out_dtype = out_dtype or x.dtype
    w = dequantize(qt, jnp.bfloat16)
    wshape = tuple(qt.shape)
    K = wshape[0]
    y = x.reshape(-1, K) @ w.reshape(K, -1)
    return y.astype(out_dtype).reshape(*x.shape[:-1], *wshape[1:])
