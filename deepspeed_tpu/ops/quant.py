"""Group-wise quantization + quantized collectives (ZeRO++ primitives).

TPU-native equivalents of the reference quantization kernels
(``csrc/quantization/`` — ``pt_binding.cpp:270-297`` exports ``quantize``/
``dequantize`` grouped sym/asym with configurable bits, ``swizzle_quant``,
``quantized_reduction`` the qgZ dequant-reduce-requant primitive,
``quantize_intX.cu`` int4/int8; and the ZeRO++ comm paths
``runtime/zero/partition_parameters.py:753`` CUDAQuantizer int8 weight
all-gather, ``runtime/comm/coalesced_collectives.py`` all_to_all_quant_reduce).

Everything is jnp — XLA fuses quantize into the surrounding collectives'
pack/unpack.  The collectives are written for use **inside shard_map**
(manual axes) so the wire format really is int8/int4:

* ``quantized_all_gather``  — qwZ: 2x less all-gather traffic than bf16.
* ``quantized_psum_scatter`` — qgZ: all-to-all int8 chunks, dequant,
  local reduce (the single-hop formulation of qgZ's
  all-to-all-based gradient reduction).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax import axis_size


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Grouped quantized representation: int data + per-group scale/zero.

    Registered as a pytree with (bits, shape, dtype) as STATIC aux data:
    quantized trees can then cross jit boundaries as ARGUMENTS (device
    buffers) instead of closure constants — a closed-over llama3-8b int8
    tree baked 7.5 GB of constants into the HLO and killed the compile."""

    __slots__ = ("data", "scale", "zero", "bits", "shape", "dtype",
                 "layout")

    def __init__(self, data, scale, zero, bits: int,
                 shape: Tuple[int, ...], dtype, layout: str = "grouped"):
        self.data = data           # int8 (packed nibbles when bits=4)
        self.scale = scale         # f32 [groups, 1]
        self.zero = zero           # f32 [groups, 1] (None when symmetric)
        self.bits = bits
        self.shape = tuple(shape)  # original shape
        self.dtype = dtype         # original dtype
        # "grouped": grouped-flat [G, gsz];  "rowwise": weight-shaped
        # int8 with leading-dim scales;  "rowwise4": flat [K/2, N] packed
        # nibbles over strided contraction halves (byte j = rows j and
        # j + K/2) with leading-dim scales — the serving GEMM layouts
        self.layout = layout

    def tree_flatten(self):
        return (self.data, self.scale, self.zero), \
            (self.bits, self.shape, jnp.dtype(self.dtype), self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, zero = children
        bits, shape, dtype, layout = aux
        return cls(data, scale, zero, bits, shape, dtype, layout)

    def __repr__(self):
        return (f"QuantizedTensor(bits={self.bits}, shape={self.shape}, "
                f"dtype={self.dtype}, layout={self.layout})")


def _group(x: jax.Array, num_groups: int) -> jax.Array:
    flat = x.reshape(-1)
    assert flat.size % num_groups == 0, \
        f"size {flat.size} not divisible into {num_groups} groups"
    return flat.reshape(num_groups, -1)


def default_groups(size: int, target_group_size: int = 2048) -> int:
    """Largest group count dividing ``size`` with groups >= the target
    group size (shared by every grouped-quant entry point)."""
    groups = max(1, size // target_group_size)
    while size % groups:
        groups -= 1
    return groups


def _pack_int4(q: jax.Array) -> jax.Array:
    """Two int4 values per int8 byte (reference: quantize_int4 layout)."""
    q = q.reshape(q.shape[0], -1, 2)
    lo = (q[..., 0] & 0x0F).astype(jnp.uint8)
    hi = (q[..., 1] & 0x0F).astype(jnp.uint8)
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_nibbles(p: jax.Array):
    """(lo, hi) int8 nibbles of a packed byte array, sign-extended from
    4-bit two's complement.  Pure jnp — shared by the grouped unpack,
    the rowwise4 dequant, and the Pallas mixed-GEMM kernel."""
    # widened to int32 first: the TPU's kernel compiler has no 8-bit
    # shifts.  On the sign-extended byte, an arithmetic >> 4 IS the
    # sign-extended high nibble, and << 28 >> 28 the low one.
    w = p.astype(jnp.int32)
    lo = ((w << 28) >> 28).astype(jnp.int8)
    hi = (w >> 4).astype(jnp.int8)
    return lo, hi


def _unpack_int4(p: jax.Array) -> jax.Array:
    lo, hi = unpack_nibbles(p)
    return jnp.stack([lo, hi], axis=-1).reshape(p.shape[0], -1)


def quantize(x: jax.Array, bits: int = 8, num_groups: Optional[int] = None,
             symmetric: bool = True,
             stochastic: bool = False,
             rng: Optional[jax.Array] = None) -> QuantizedTensor:
    """Group-wise quantization (reference: ds_quantize_* /
    ds_sr_quantize_* sym/asym families)."""
    assert bits in (4, 8), bits
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    if num_groups is None:
        num_groups = default_groups(x.size)
    g = _group(x.astype(jnp.float32), num_groups)
    qmax = float(2 ** (bits - 1) - 1)          # 127 / 7
    qmin = -qmax - 1
    if symmetric:
        scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / qmax
        scale = jnp.where(scale == 0, 1.0, scale)
        zero = None
        t = g / scale
    else:
        gmin = jnp.min(g, axis=1, keepdims=True)
        gmax = jnp.max(g, axis=1, keepdims=True)
        scale = (gmax - gmin) / (qmax - qmin)
        scale = jnp.where(scale == 0, 1.0, scale)
        zero = gmin - qmin * scale
        t = (g - zero) / scale
    if stochastic:
        # stochastic rounding (reference: ds_sr_quantize_*)
        assert rng is not None, "stochastic quantization needs rng"
        t = jnp.floor(t + jax.random.uniform(rng, t.shape))
    else:
        t = jnp.round(t)
    q = jnp.clip(t, qmin, qmax).astype(jnp.int8)
    if bits == 4:
        q = _pack_int4(q)
    return QuantizedTensor(q, scale, zero, bits, orig_shape, orig_dtype)


def quantize_rowwise(x: jax.Array, bits: int = 8) -> QuantizedTensor:
    """int8 quantization with per-FIRST-DIM scales and data kept in the
    WEIGHT'S OWN SHAPE (no grouped-flat relayout).

    This is the serving-weight layout: the grouped-flat form's
    dequantize chain profiles as convert → reshape → LAYOUT COPY →
    matmul on TPU (the [G, gsz] tiling never matches the matmul
    operand's), ~6x the int8 bytes of HBM traffic per use.  Row-wise,
    the scale broadcasts along the trailing dims and the int8→bf16
    convert+multiply fuses into the matmul operand load."""
    assert bits == 8, "row-wise layout is int8-only (int4 packs lanes)"
    return _quantize_leading(x, lead_dims=1)


def _quantize_leading(x: jax.Array, lead_dims: int) -> QuantizedTensor:
    """Row-wise quantization generalized to ``lead_dims`` leading scale
    dims (stacked [L, rows, ...] weights use lead_dims=2)."""
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    red = tuple(range(lead_dims, x.ndim))
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=red,
                    keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -128, 127)
    return QuantizedTensor(q.astype(jnp.int8), scale, None, 8,
                           orig_shape, orig_dtype, layout="rowwise")


def is_rowwise_int8(qt: "QuantizedTensor") -> bool:
    """The layout the int8 mixed-input GEMM consumes (ops/mixed_gemm.py):
    symmetric int8 payload kept in the weight's own shape with leading-
    dim scales — the single source of truth for eligibility checks."""
    return (qt.bits == 8 and qt.zero is None
            and tuple(qt.data.shape) == tuple(qt.shape))


def is_rowwise_int4(qt: "QuantizedTensor") -> bool:
    """The packed layout the int4 mixed-input GEMM consumes: flat
    [K/2, N] strided-half nibbles with leading-dim scales
    (:func:`quantize_rowwise4`)."""
    return qt.bits == 4 and qt.zero is None and qt.layout == "rowwise4"


def is_mixed_gemm_layout(qt: "QuantizedTensor") -> bool:
    """Any layout the mixed-input GEMM family consumes natively."""
    return is_rowwise_int8(qt) or is_rowwise_int4(qt)


def quantize_rowwise4(x: jax.Array, contract_dims: int = 1,
                      lead_dims: int = 0) -> QuantizedTensor:
    """Packed int4 serving layout (reference analog: the FP6/int4
    weight-only GEMM's prepacked storage,
    inference/v2/kernels/core_ops/cuda_linear/linear_kernels_cuda.cu —
    real 0.5-byte/weight storage AND bandwidth, not emulation).

    ``x``: [*lead, K..., N...] where the first ``contract_dims`` dims
    after ``lead_dims`` stack dims flatten into the contraction K.
    Symmetric per-(lead, first-K-dim-row) scales, values in [-7, 7],
    and the flat contraction packed as STRIDED HALVES: byte row j holds
    flat rows j (lo nibble) and j + K/2 (hi nibble).  The strided split
    means unpacking is two contiguous row blocks — no lane interleave —
    which both the XLA dequant and the Pallas kernel exploit."""
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    lead = orig_shape[:lead_dims]
    K = int(np.prod(orig_shape[lead_dims:lead_dims + contract_dims]))
    N = int(np.prod(orig_shape[lead_dims + contract_dims:]) or 1)
    assert K % 2 == 0, f"int4 packing needs an even contraction ({K})"
    red = tuple(range(lead_dims + 1, x.ndim))
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=red,
                    keepdims=False) / 7.0
    scale = jnp.where(scale == 0, 1.0, scale)       # [*lead, S]
    S = scale.shape[-1]
    sb = scale.reshape(*lead, S, *([1] * (x.ndim - lead_dims - 1)))
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sb), -7, 7)
    q = q.astype(jnp.int8).reshape(*lead, K, N)
    lo, hi = q[..., : K // 2, :], q[..., K // 2:, :]
    packed = ((lo & 0x0F) | ((hi & 0x0F) << 4)).astype(jnp.int8)
    return QuantizedTensor(packed, scale.reshape(*lead, S, 1), None, 4,
                           orig_shape, orig_dtype, layout="rowwise4")


def dequantize_rowwise4(qt: QuantizedTensor, dtype=None) -> jax.Array:
    """Unpack a :func:`quantize_rowwise4` payload back to the original
    weight shape (the XLA fallback path; the kernel unpacks in VMEM)."""
    out_dt = dtype or qt.dtype
    lo, hi = unpack_nibbles(qt.data)                # [*lead, K/2, N]
    flat = jnp.concatenate([lo, hi], axis=-2)       # [*lead, K, N]
    K, N = flat.shape[-2], flat.shape[-1]
    s = qt.scale.reshape(*qt.scale.shape[:-1])      # [*lead, S]
    S = s.shape[-1]
    w = flat.reshape(*flat.shape[:-2], S, K // S, N).astype(out_dt) \
        * s[..., None, None].astype(out_dt)
    return w.reshape(qt.shape).astype(out_dt)


def dequantize(qt: QuantizedTensor, dtype=None) -> jax.Array:
    """(reference: dequantize / dequantize_int4_to_half_experimental)."""
    if qt.layout == "rowwise4":
        return dequantize_rowwise4(qt, dtype)
    out_dt = dtype or qt.dtype
    q = _unpack_int4(qt.data) if qt.bits == 4 else qt.data
    if qt.bits == 8 and qt.zero is None \
            and tuple(q.shape) == tuple(qt.shape):
        # row-wise layout: no reshape, scale broadcasts; computing in
        # the output dtype lets XLA fuse convert+mul into the consumer
        # instead of materializing an f32 copy of the whole weight
        return q.astype(out_dt) * qt.scale.astype(out_dt)
    g = q.astype(jnp.float32) * qt.scale
    if qt.zero is not None:
        g = g + qt.zero
    return g.reshape(qt.shape).astype(out_dt)


def quantized_reduction(qts, dtype=jnp.float32) -> jax.Array:
    """Dequantize-and-mean over a sequence of quantized tensors — the qgZ
    core primitive (reference: quant_reduce.cu ``quantized_reduction``)."""
    acc = dequantize(qts[0], jnp.float32)
    for qt in qts[1:]:
        acc = acc + dequantize(qt, jnp.float32)
    return (acc / len(qts)).astype(dtype)


# --------------------------------------------------------------------------
# Quantized collectives — call INSIDE shard_map (manual mesh axes)
# --------------------------------------------------------------------------

def quantized_all_gather(x: jax.Array, axis_name: str, bits: int = 8,
                         num_groups: Optional[int] = None,
                         gather_dim: int = 0) -> jax.Array:
    """qwZ: quantize the local shard, all-gather int data + scales,
    dequantize (reference: CUDAQuantizer gather path
    partition_parameters.py:753 + AllGatherCoalescedHandle.wait dequant
    partition_parameters.py:675).  Wire bytes: 1/2 (int8) or 1/4 (int4)
    of bf16."""
    qt = quantize(x, bits=bits, num_groups=num_groups)
    data = jax.lax.all_gather(qt.data, axis_name)          # [n, ...]
    scale = jax.lax.all_gather(qt.scale, axis_name)
    n = data.shape[0]
    parts = [dequantize(QuantizedTensor(data[i], scale[i], None, bits,
                                        qt.shape, qt.dtype))
             for i in range(n)]
    return jnp.concatenate(parts, axis=gather_dim)


def quantized_psum_scatter(x: jax.Array, axis_name: str, bits: int = 8,
                           num_groups: Optional[int] = None,
                           mean: bool = False,
                           pad: bool = False) -> jax.Array:
    """qgZ single-hop: split the local (unreduced) tensor into one chunk
    per rank along dim 0, quantize each, all-to-all, dequantize and reduce
    locally (reference: all_to_all_quant_reduce
    runtime/comm/coalesced_collectives.py + quant_reduce.cu).  Wire bytes:
    int8/int4 instead of fp32 — 4-8x less reduce traffic.

    ``pad``: a dim 0 the axis does not divide is zero-filled up to the
    next multiple of the axis size and the PADDED per-rank shard is
    returned (callers slice; ``quantized_all_reduce``'s padding path
    does).  Off, a non-divisible shape asserts — the historical
    contract, which keeps accidental layout changes loud."""
    n = axis_size(axis_name)
    if pad and x.shape[0] % n:
        pad_rows = (-x.shape[0]) % n
        x = jnp.concatenate(
            [x, jnp.zeros((pad_rows,) + x.shape[1:], x.dtype)])
    assert x.shape[0] % n == 0, (x.shape, n)
    if bits == 4:
        # packed nibbles need an even group size; fold the group count
        # (keeping it a divisor of the per-destination chunk — the
        # scale regrouping below depends on that) until it is
        per_chunk = x.size // n
        ng = num_groups if num_groups is not None \
            else default_groups(per_chunk)
        while ng > 1 and (per_chunk % ng or (per_chunk // ng) % 2):
            ng -= 1
        assert (per_chunk // ng) % 2 == 0, \
            f"int4 quantized scatter needs an even chunk size ({per_chunk})"
        num_groups = ng
    chunks = x.reshape(n, x.shape[0] // n, *x.shape[1:])
    if num_groups is None:
        # per-destination-chunk grouping at the shared default group size
        # (one scale per whole chunk would let a single outlier wipe the
        # rest of the chunk's signal — reference uses ~2048-elem groups)
        num_groups = default_groups(x.size // n)
    qt = quantize(chunks, bits=bits, num_groups=num_groups * n)
    # regroup so each destination's scales travel with its data
    data = qt.data.reshape(n, -1)
    scale = qt.scale.reshape(n, -1)
    data = jax.lax.all_to_all(data, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    scale = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    per_rank_shape = chunks.shape[1:]
    acc = jnp.zeros(per_rank_shape, jnp.float32)
    groups_per_rank = qt.scale.shape[0] // n
    for i in range(n):
        q_i = QuantizedTensor(
            data[i].reshape(groups_per_rank, -1),
            scale[i].reshape(groups_per_rank, 1), None, bits,
            per_rank_shape, jnp.float32)
        acc = acc + dequantize(q_i)
    if mean:
        acc = acc / n
    return acc.astype(x.dtype)


def quantized_psum_scatter_dim(x: jax.Array, axis_name: str, dim: int = 0,
                               bits: int = 8) -> jax.Array:
    """``quantized_psum_scatter`` along an arbitrary dimension (the qgZ
    reduce-scatter leg for a grad leaf whose sharded dim isn't 0)."""
    if dim != 0:
        x = jnp.moveaxis(x, dim, 0)
    out = quantized_psum_scatter(x, axis_name, bits=bits)
    if dim != 0:
        out = jnp.moveaxis(out, 0, dim)
    return out


def quantized_all_reduce(x: jax.Array, axis_name: str,
                         bits: int = 8, pad: bool = False) -> jax.Array:
    """Quantized-wire all-reduce: int reduce-scatter + int all-gather.
    2 int8 bytes per element on the wire instead of 4 fp32 (reference:
    the fallback ``all_to_all_quant_reduce`` path of
    coalesced_collectives.py for tensors every rank keeps whole).

    A dim 0 the axis does not divide falls back to plain psum by
    default (the historical qgZ contract: tiny leaves ride the exact
    wire and training numerics stay put) — with ``pad=True`` it
    instead runs the padding path: flatten, zero-fill to a multiple of
    the axis size, quantized reduce, slice back.  The serving
    activation path (comm/overlap.py) opts into padding so every
    eligible reduction really rides the quantized wire."""
    n = axis_size(axis_name)
    if x.ndim == 0 or n == 1:
        return jax.lax.psum(x, axis_name)
    # shapes the direct scatter cannot take: a dim 0 the axis does not
    # divide, or (int4 packs two codes per byte) an odd per-rank chunk
    awkward = x.shape[0] % n or (bits == 4 and (x.size // n) % 2)
    if awkward:
        if not pad:
            return jax.lax.psum(x, axis_name)
        flat = x.reshape(-1)
        mult = n * (2 if bits == 4 else 1)
        fill = (-flat.shape[0]) % mult
        if fill:
            flat = jnp.concatenate(
                [flat, jnp.zeros((fill,), flat.dtype)])
        red = quantized_psum_scatter(flat, axis_name, bits=bits,
                                     pad=True)
        out = quantized_all_gather(red, axis_name, bits=bits,
                                   gather_dim=0)
        return out[:x.size].reshape(x.shape).astype(x.dtype)
    red = quantized_psum_scatter(x, axis_name, bits=bits)
    return quantized_all_gather(red, axis_name, bits=bits, gather_dim=0)


_FP8_FORMATS = {
    "fp8_e4m3": (jnp.float8_e4m3fn, 448.0),
    "fp8_e5m2": (jnp.float8_e5m2, 57344.0),
}


def fp_quantize(x: jax.Array, fmt: str = "fp8_e4m3",
                num_groups: Optional[int] = None) -> QuantizedTensor:
    """Float-to-float quantization (reference: csrc/fp_quantizer/
    fp_quantize.cpp — FP6/FP8/FP12 ``quantize``/``get_scales``).  TPU has
    native fp8 dtypes; per-group scales stretch each group onto the
    format's dynamic range.  FP6/FP12 have no hardware type here — use
    grouped int quantization (``quantize``) for sub-byte widths."""
    if fmt not in _FP8_FORMATS:
        raise ValueError(f"unknown fp format {fmt!r}; "
                         f"known: {sorted(_FP8_FORMATS)}")
    dtype, fmax = _FP8_FORMATS[fmt]
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    if num_groups is None:
        num_groups = default_groups(x.size)
    g = _group(x.astype(jnp.float32), num_groups)
    scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / fmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (g / scale).astype(dtype)
    return QuantizedTensor(q, scale, None, 8, orig_shape, orig_dtype)


def swizzle_quant(x: jax.Array, bits: int = 8,
                  num_groups: Optional[int] = None) -> QuantizedTensor:
    """Layout-compat shim (reference: swizzle_quant — an interleaved
    layout for hierarchical all-to-all on NVLink+IB topologies).  XLA owns
    collective layouts on TPU, so this is plain grouped quantization."""
    return quantize(x, bits=bits, num_groups=num_groups)


# --------------------------------------------------------------------------
# 1-bit collectives (reference: runtime/comm/nccl.py:16 compressed_allreduce
# — cupy sign packing + per-chunk scale; the wire format behind
# OnebitAdam/ZeroOneAdam/OnebitLamb's up-to-5x comm reduction,
# docs/_tutorials/onebit-adam.md:2)
# --------------------------------------------------------------------------

def pack_signs(x: jax.Array) -> jax.Array:
    """[n] floats -> [n/8] uint8 of sign bits (1 = non-negative)."""
    n = x.shape[0]
    assert n % 8 == 0, f"pack_signs needs n % 8 == 0, got {n}"
    bits = (x >= 0).astype(jnp.uint8).reshape(n // 8, 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return (bits << shifts).sum(axis=1).astype(jnp.uint8)


def unpack_signs(p: jax.Array) -> jax.Array:
    """[n/8] uint8 -> [n] float32 in {-1, +1}."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (p[:, None] >> shifts) & 1
    return jnp.where(bits.reshape(-1) > 0, 1.0, -1.0).astype(jnp.float32)


def onebit_all_reduce(x: jax.Array, axis_name, err: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Error-compensated 1-bit mean-allreduce.

    Each shard sends sign bits (1/32 of fp32) + one fp32 scale
    (mean |x + err|); the mean of the per-shard sign*scale
    reconstructions comes back, and the local compression residual
    becomes the next step's error feedback.  Place at the DP gradient /
    momentum reduction boundary under ``shard_map`` (the engine's manual
    reduce region or a custom training loop).

    Returns (mean_reduced, new_err)."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    if err is not None:
        flat = flat + err.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % 8
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    c = flat
    scale = jnp.mean(jnp.abs(c[:n])) if pad else jnp.mean(jnp.abs(c))
    packed = pack_signs(c)
    local_q = jnp.where(c >= 0, scale, -scale)
    new_err = (c - local_q)[:n].reshape(shape).astype(dtype)

    all_packed = jax.lax.all_gather(packed, axis_name)     # [W, n/8] u8
    all_scale = jax.lax.all_gather(scale, axis_name)       # [W]
    W = all_packed.shape[0]
    signs = jax.vmap(unpack_signs)(all_packed)             # [W, n]
    mean = (signs * all_scale[:, None]).mean(axis=0)
    return mean[:n].reshape(shape).astype(dtype), new_err


# --------------------------------------------------------------------------
# Emulated minifloat formats + selective dequantize (reference:
# csrc/fp_quantizer — FP6 e3m2 / FP12 quantize + selective_dequantize used
# to expand only the rows a step touches, e.g. routed MoE experts)
# --------------------------------------------------------------------------

import functools


@functools.lru_cache(maxsize=None)
def _minifloat_table(exp_bits: int, man_bits: int) -> np.ndarray:
    """All non-negative representable values of a (1, e, m) minifloat
    with IEEE-style subnormals, ascending."""
    bias = (1 << (exp_bits - 1)) - 1
    vals = []
    for e in range(1 << exp_bits):
        for m in range(1 << man_bits):
            if e == 0:
                v = (m / (1 << man_bits)) * 2.0 ** (1 - bias)
            else:
                v = (1 + m / (1 << man_bits)) * 2.0 ** (e - bias)
            vals.append(v)
    return np.asarray(vals, np.float32)


_MINIFLOAT_FORMATS = {
    # name: (exp_bits, man_bits, container dtype)
    "fp6_e3m2": (3, 2, jnp.int8),
    "fp12_e4m7": (4, 7, jnp.int16),
}

# the single source of truth for weight-quant format names (serving
# config strings), bit widths, and minifloat format ids
WEIGHT_QUANT_BITS = {"int8": 8, "int4": 4, "fp6": 6, "fp12": 12}
MINIFLOAT_BY_BITS = {6: "fp6_e3m2", 12: "fp12_e4m7"}


def dequantize_any(qt: "QuantizedTensor", dtype=None) -> jax.Array:
    """Dispatch on layout/bit width: packed row-wise fp6, emulated
    minifloat (6/12), or grouped/row-wise int (4/8)."""
    if qt.layout == "rowwise6":
        return dequantize_rowwise6(qt, dtype)
    if qt.layout == "rowwise12":
        return dequantize_rowwise12(qt, dtype)
    if qt.bits in MINIFLOAT_BY_BITS:
        return minifloat_dequantize(qt, dtype)
    return dequantize(qt, dtype)


def minifloat_quantize(x: jax.Array, fmt: str = "fp6_e3m2",
                       num_groups: Optional[int] = None) -> QuantizedTensor:
    """Emulated FP6/FP12 grouped quantization: per-group scale onto the
    format's dynamic range, then nearest representable value; codes are
    stored in the smallest integer container (1 byte for fp6, 2 for
    fp12 — the reference packs 6-bit lanes the same way on GPUs without
    native types)."""
    if fmt not in _MINIFLOAT_FORMATS:
        raise ValueError(f"unknown minifloat format {fmt!r}; "
                         f"known: {sorted(_MINIFLOAT_FORMATS)}")
    eb, mb, container = _MINIFLOAT_FORMATS[fmt]
    table = _minifloat_table(eb, mb)
    fmax = float(table[-1])
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    if num_groups is None:
        num_groups = default_groups(x.size)
    g = _group(x.astype(jnp.float32), num_groups)
    scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / fmax
    scale = jnp.where(scale == 0, 1.0, scale)
    t = g / scale
    mags = jnp.abs(t)
    tab = jnp.asarray(table)
    # nearest representable: searchsorted against midpoints
    mids = jnp.asarray((table[1:] + table[:-1]) / 2.0)
    code = jnp.searchsorted(mids, mags).astype(jnp.int32)
    signed = jnp.where(t < 0, -code - 1, code)     # sign folded into code
    qt = QuantizedTensor(signed.astype(container), scale, None,
                         eb + mb + 1, orig_shape, orig_dtype)
    return qt


def minifloat_dequantize(qt: QuantizedTensor, dtype=None) -> jax.Array:
    fmt = MINIFLOAT_BY_BITS[qt.bits]
    eb, mb, _ = _MINIFLOAT_FORMATS[fmt]
    tab = jnp.asarray(_minifloat_table(eb, mb))
    code = qt.data.astype(jnp.int32)
    mag = tab[jnp.where(code < 0, -code - 1, code)]
    val = jnp.where(code < 0, -mag, mag) * qt.scale
    return val.reshape(qt.shape).astype(dtype or qt.dtype)


def _pack_codes(u: jax.Array, per_word: int, bits: int) -> jax.Array:
    """[..., N] codes → packed bytes: ``per_word`` codes per 24-bit word
    (3 bytes), little-endian bit order.  Serves the fp6 (4×6b) and fp12
    (2×12b) layouts."""
    g = u.astype(jnp.uint32).reshape(*u.shape[:-1], -1, per_word)
    word = g[..., 0]
    for i in range(1, per_word):
        word = word | (g[..., i] << (bits * i))
    b = jnp.stack([word & 0xFF, (word >> 8) & 0xFF, (word >> 16) & 0xFF],
                  axis=-1).astype(jnp.uint8)
    return b.reshape(*u.shape[:-1], -1)


def _unpack_codes(p: jax.Array, per_word: int, bits: int) -> jax.Array:
    """[..., 3M] bytes → [..., per_word*M] codes."""
    b = p.astype(jnp.uint32).reshape(*p.shape[:-1], -1, 3)
    word = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
    mask = (1 << bits) - 1
    codes = jnp.stack([(word >> (bits * i)) & mask
                       for i in range(per_word)], axis=-1)
    return codes.reshape(*p.shape[:-1], -1).astype(jnp.int32)


# (fmt, codes per 24-bit word, code bits, layout tag)
_PACKED_MINIFLOAT = {
    "rowwise6": ("fp6_e3m2", 4, 6),
    "rowwise12": ("fp12_e4m7", 2, 12),
}


def _quantize_rowwise_minifloat(x: jax.Array, layout: str,
                                lead_dims: int = 0) -> QuantizedTensor:
    """REAL packed minifloat weight storage (reference:
    csrc/fp_quantizer/fp_quantize.cu + the cuda_linear FP6 GEMM's
    prepacked weights — the emulated :func:`minifloat_quantize` spends a
    whole integer container per value).  Sign-magnitude codes packed
    along the LAST dim, symmetric per-leading-row scales like the other
    serving layouts; fp6 = 0.75 and fp12 = 1.5 bytes/element."""
    fmt, per_word, bits = _PACKED_MINIFLOAT[layout]
    eb, mb, _ = _MINIFLOAT_FORMATS[fmt]
    table = _minifloat_table(eb, mb)
    fmax = float(table[-1])
    sign_bit = 1 << (bits - 1)
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    assert orig_shape[-1] % per_word == 0, (orig_shape, per_word)
    assert x.ndim > lead_dims + 1, (
        f"{layout} needs at least one data dim beyond the scale rows "
        f"(shape {orig_shape}, lead_dims={lead_dims})")
    red = tuple(range(lead_dims + 1, x.ndim))
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=red,
                    keepdims=False) / fmax
    scale = jnp.where(scale == 0, 1.0, scale)
    S = scale.shape[-1]
    sb = scale.reshape(*scale.shape, *([1] * (x.ndim - lead_dims - 1)))
    t = x.astype(jnp.float32) / sb
    mids = jnp.asarray((table[1:] + table[:-1]) / 2.0)
    mag = jnp.searchsorted(mids, jnp.abs(t)).astype(jnp.uint32)
    ucode = jnp.where(t < 0, mag | sign_bit, mag)
    return QuantizedTensor(_pack_codes(ucode, per_word, bits),
                           scale.reshape(*scale.shape[:lead_dims], S, 1),
                           None, eb + mb + 1, orig_shape, orig_dtype,
                           layout=layout)


def _dequantize_rowwise_minifloat(qt: QuantizedTensor,
                                  dtype=None) -> jax.Array:
    out_dt = dtype or qt.dtype
    fmt, per_word, bits = _PACKED_MINIFLOAT[qt.layout]
    eb, mb, _ = _MINIFLOAT_FORMATS[fmt]
    tab = jnp.asarray(_minifloat_table(eb, mb))
    sign_bit = 1 << (bits - 1)
    codes = _unpack_codes(qt.data, per_word, bits)
    mag = tab[codes & (sign_bit - 1)]
    val = jnp.where((codes & sign_bit) != 0, -mag, mag)
    s = qt.scale.reshape(*qt.scale.shape[:-1])       # [*lead, S]
    val = val.reshape(*s.shape, -1, codes.shape[-1])
    out = val * s[..., None, None]
    return out.reshape(qt.shape).astype(out_dt)


def quantize_rowwise6(x: jax.Array, lead_dims: int = 0) -> QuantizedTensor:
    return _quantize_rowwise_minifloat(x, "rowwise6", lead_dims)


def dequantize_rowwise6(qt: QuantizedTensor, dtype=None) -> jax.Array:
    return _dequantize_rowwise_minifloat(qt, dtype)


def quantize_rowwise12(x: jax.Array, lead_dims: int = 0) -> QuantizedTensor:
    return _quantize_rowwise_minifloat(x, "rowwise12", lead_dims)


def dequantize_rowwise12(qt: QuantizedTensor, dtype=None) -> jax.Array:
    return _dequantize_rowwise_minifloat(qt, dtype)


def selective_dequantize(qt: QuantizedTensor, rows: jax.Array,
                         dtype=None) -> jax.Array:
    """Dequantize only the selected first-dim rows of a grouped
    QuantizedTensor (reference: selective_dequantize fp_quantizer — the
    MoE path expands just the routed experts' weights).

    Requires the grouping to not straddle rows (row size a multiple of
    the group size), which ``default_groups`` guarantees whenever the
    first dim divides the group count."""
    n_rows = qt.shape[0]
    G = qt.data.shape[0]
    if G % n_rows:
        raise ValueError(
            f"groups ({G}) must align with rows ({n_rows}) for "
            "selective dequantize; quantize with num_groups a multiple "
            "of the first dim")
    gpr = G // n_rows                       # groups per row
    rows = jnp.asarray(rows, jnp.int32)
    gidx = (rows[:, None] * gpr + jnp.arange(gpr)[None, :]).reshape(-1)
    sub = QuantizedTensor(
        qt.data[gidx], qt.scale[gidx],
        None if qt.zero is None else qt.zero[gidx],
        qt.bits, (int(rows.shape[0]),) + tuple(qt.shape[1:]), qt.dtype)
    return dequantize_any(sub, dtype)
