"""Ragged-batch model forward with paged KV cache.

TPU-native analog of the reference's FastGen model layer
(``inference/v2/model_implementations/inference_transformer_base.py:48``
building per-layer DSModules, and the ragged kernel suite
``linear_blocked_kv_rotary`` (QKV+rotary written straight into paged KV),
``blocked_flash`` (paged attention over block tables), ``ragged_embed``,
``logits_gather`` (last-token-only unembed) — SURVEY §2.2/§3.4).

One jit-compiled function processes a fixed token budget T of mixed
prefill/decode tokens (Dynamic SplitFuse's fixed-shape forward is exactly
XLA-friendly):
  embed [T] → per layer: qkv + rope(positions) → scatter K/V into the
  paged cache → per-token attention over the owning sequence's block
  table → mlp/moe → final norm → unembed only at each sequence's last
  scheduled token.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..comm.overlap import (ServingComm, shard_matmul_allgather,
                            shard_matmul_allreduce)
from ..models import layers as L
from ..models.transformer import (TransformerConfig, _norm, _qk_norm,
                                  moe_share, stack_layer)
from .ragged.state import RaggedBatch
from .sampler import row_keys, window_keys


# rows a sparse-expert model's serving step appends to its sampled tokens
# (``pipelined_ragged_step``): assignments, 1000 x load max over mean,
# experts that took a row
MOE_STAT_ROWS = 3


def moe_stat_rows(cfg: TransformerConfig) -> int:
    """``MOE_STAT_ROWS``, one more for a model with experts that compute
    nothing (the assignments to them), and one for a share that holds
    whole device groups (the rows that opened one of them)."""
    return MOE_STAT_ROWS + bool(cfg.moe_zero_experts) \
        + (cfg.held_groups is not None)

_KV_QMAX = {jnp.dtype(jnp.int8): 127.0,
            jnp.dtype(jnp.float8_e4m3fn): 448.0}


def _kv_parts(kv_layer):
    """(data, scales-or-None) view of a paged cache operand — quantized
    caches travel as a (data, scales) tuple pytree."""
    if isinstance(kv_layer, tuple):
        return kv_layer[0], kv_layer[1]
    return kv_layer, None


def _quantize_kv(x, qdt):
    """x: [..., D] → (codes [..., D] in ``qdt``, scales [...] f32) with
    one symmetric scale per trailing vector."""
    xf = x.astype(jnp.float32)
    qmax = _KV_QMAX[jnp.dtype(qdt)]
    scale = jnp.max(jnp.abs(xf), axis=-1) / qmax
    scale = jnp.maximum(scale, 1e-8)
    q = xf / scale[..., None]
    if jnp.dtype(qdt) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(q), -127, 127)
    return q.astype(qdt), scale


def _dequant_ctx(data, scales, dt):
    """data: [..., D] codes, scales: [...] → [..., D] in ``dt``."""
    return (data.astype(jnp.float32)
            * scales[..., None]).astype(dt)


def _layer_of(pool, layer):
    """``(base, rows)``: where in ``pool`` the layer at hand keeps its
    ``rows`` rows (its blocks and, last, its trash row).  ``None`` is a
    pool that holds one layer and nothing else; the layer scan passes
    ``(li * rows, rows)`` for layer ``li`` of the stacked cache viewed
    as ``[L * rows, ...]``."""
    return (0, _kv_parts(pool)[0].shape[0]) if layer is None else layer


def _layer_tables(tables, layer):
    """Block ids gathered from ``RaggedBatch.block_tables`` → rows of
    the pool: the -1 pads go to the layer's trash row, then every id
    moves by the layer's base.  The cache write and all three attention
    formulations index the pool by these rows and nothing else, which
    is how they address ``(layer, block)`` without a slice.  Applied
    AFTER the gather, so the gather itself reads the same table for
    every layer."""
    base, rows = layer
    return jnp.where(tables < 0, rows - 1, tables) + base


def _write_kv(kv_layer, k, v, batch: RaggedBatch, block_size: int,
              layer=None):
    """Scatter per-token K/V into the paged cache (quantizing on write
    when the cache is a (data, scales) pair).

    kv_layer: [blocks, bs, 2, Hkv, D]; k/v: [T, Hkv, D]
    (reference kernel: linear_blocked_kv_rotary / linear_kv_copy).
    ``layer``: see ``_layer_of``.
    """
    data, scales = _kv_parts(kv_layer)
    base, rows = _layer_of(kv_layer, layer)
    blk = batch.block_tables[batch.seq_slot,
                             batch.positions // block_size]      # [T]
    # budget-padding tokens write to the trash block (last row) so they
    # can never clobber a live sequence's KV
    blk = jnp.where(batch.token_valid, blk + base, base + rows - 1)
    off = batch.positions % block_size                           # [T]
    if scales is None:
        data = data.at[blk, off, 0].set(k)
        data = data.at[blk, off, 1].set(v)
        return data
    kq, ks = _quantize_kv(k, data.dtype)
    vq, vs = _quantize_kv(v, data.dtype)
    data = data.at[blk, off, 0].set(kq)
    data = data.at[blk, off, 1].set(vq)
    # a block's scales are [Hkv, 2 * bs]: a head's keys', then its values'
    scales = scales.at[blk, :, off].set(ks)
    scales = scales.at[blk, :, block_size + off].set(vs)
    return (data, scales)


def _block_scales(scales):
    """Gathered blocks' scales ``[..., Hkv, 2 * bs]`` (a head a row, its
    keys' then its values': what the kernel's DMAs want whole,
    ``KVCacheConfig.kv_zeros``) → ``[..., bs, 2, Hkv]``, laid like the
    blocks' codes."""
    *lead, heads, lanes = scales.shape
    x = scales.reshape(*lead, heads, 2, lanes // 2)
    return jnp.swapaxes(jnp.moveaxis(x, -3, -1), -3, -2)


def _pool_scales(scales):
    """``_block_scales`` undone: scales laid like the blocks' codes
    ``[..., bs, 2, Hkv]`` (what ``_quantize_kv`` gives for a pool's
    worth of keys and values) → the pool's ``[..., Hkv, 2 * bs]``."""
    x = jnp.moveaxis(jnp.swapaxes(scales, -3, -2), -1, -3)
    return x.reshape(*x.shape[:-2], -1)


def _fill_heads(x, num_kv_heads: int, groups: int, slab):
    """x: ``[T, num_kv_heads * r, D]`` → ``[T, slab[0] * r, slab[1]]``: a
    layer's queries, keys or values with the heads and lanes of a pool
    allocated for the kernel (``KVCacheConfig.tiled``: each of the
    ``groups`` chips' kv heads, ``r`` query heads each, and the head's
    lanes filled up with zeros); as they are where the pool holds the
    model's own."""
    T, H, D = x.shape
    heads, lanes = slab
    if (heads, lanes) == (num_kv_heads, D):
        return x
    mine = num_kv_heads // groups
    x = x.reshape(T, groups, mine, H // num_kv_heads, D)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, heads // groups - mine), (0, 0),
                    (0, lanes - D)))
    return x.reshape(T, -1, lanes)


def _cut_heads(o, num_kv_heads: int, groups: int, num_heads: int, D: int):
    """``_fill_heads`` undone on the attention's output."""
    T, H, lanes = o.shape
    if (H, lanes) == (num_heads, D):
        return o
    r = num_heads // num_kv_heads
    o = o.reshape(T, groups, H // (groups * r), r, lanes)
    return o[:, :, :num_kv_heads // groups, :, :D].reshape(T, num_heads, D)


def _query_tiles(kv, batch: RaggedBatch, block_size: int,
                 max_blocks_per_seq: int):
    """The step's query tiles for the Pallas kernel
    (``ops/paged_attention.query_tiles``): built once a step, outside
    the layer scan, block-table rows gathered per tile.  ``kv``: the
    cache, stacked ``[L, rows, ...]`` or one layer's ``[rows, ...]``
    (its last row is the trash row either way)."""
    from ..ops.paged_attention import query_tiles

    return query_tiles(batch.seq_slot, batch.positions, batch.token_valid,
                       batch.block_tables, block_size, max_blocks_per_seq,
                       trash=_kv_parts(kv)[0].shape[-5] - 1)


def _latent_tiles(cfg, pool, batch: RaggedBatch, block_size: int,
                  max_blocks_per_seq: int):
    """The step's tiles for the latent layers' kernel (``ops/mla.py``
    ``latent_tiles``): cut once a step, outside the layer scan, as
    ``_query_tiles`` are for the paged kernel.  ``pool``: the latent
    pool ``[L, rows, bs, row]`` (a layer's last row is its trash
    block).  The runs long enough for the expanded form
    (``expand_from``) make a third list."""
    from ..ops.mla import latent_tiles, wide_cut

    dims = cfg.mla_dims
    return latent_tiles(batch.seq_slot, batch.positions, batch.token_valid,
                        batch.block_tables, block_size, max_blocks_per_seq,
                        trash=pool.shape[-3] - 1, heads=dims.heads,
                        wide=wide_cut(dims))


def _paged_attention_pallas(kv_layer, q, batch: RaggedBatch,
                            block_size: int, max_blocks_per_seq: int,
                            scale: float, shard_mesh=None, slopes=None,
                            layer=None, tiles=None, window=None):
    """Pallas streaming kernel behind the same signature
    (ops/paged_attention.py — reference: blocked_flash).

    With ``shard_mesh`` (TP serving), the kernel runs under ``shard_map``:
    attention is embarrassingly parallel over heads, so each chip streams
    only its own head group's KV blocks (kv head-split on the ``tensor``
    mesh axis) — the TPU analog of the reference's TP-aware blocked_flash
    dispatch (inference/v2/model_implementations/sharding/attn.py).
    ``layer``: ``(base, rows)`` of the layer inside a stacked pool, see
    the kernel.  ``tiles``: ``_query_tiles`` of the step, which
    ``ragged_forward`` builds once for all its layers (``None``: built
    here, for a caller with one layer).  ``window``: a window layer's
    window (the kernel skips what lies behind it)."""
    from ..ops.paged_attention import paged_attention

    if tiles is None:
        tiles = _query_tiles(kv_layer, batch, block_size,
                             max_blocks_per_seq)
    if shard_mesh is None:
        return paged_attention(kv_layer, q, tiles, scale, slopes=slopes,
                               layer=layer, window=window)
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import TENSOR_AXIS

    data_spec = P(None, None, None, TENSOR_AXIS, None)  # [blocks,bs,2,Hkv,D]
    kv_spec = (data_spec if not isinstance(kv_layer, tuple)
               else (data_spec, P(None, TENSOR_AXIS, None)))
    q_spec = P(None, TENSOR_AXIS, None)               # [T, H, D]
    base, rows = _layer_of(kv_layer, layer)
    # the tile list is the same on every chip: heads split, rows do not
    in_specs = [kv_spec, q_spec, jax.tree.map(lambda _: P(), tiles), P()]
    operands = [kv_layer, q, tiles, jnp.asarray(base, jnp.int32)]
    if slopes is not None:
        in_specs.append(P(TENSOR_AXIS, None))   # slopes [Hkv, rep] split
        operands.append(jnp.asarray(slopes, jnp.float32).reshape(
            _kv_parts(kv_layer)[0].shape[3], -1))   # with the kv heads
    f = shard_map(
        lambda kvl, qq, tl, b, *sl: paged_attention(
            kvl, qq, tl, scale, slopes=sl[0] if sl else None,
            layer=(b, rows), window=window),
        mesh=shard_mesh,
        in_specs=tuple(in_specs),
        out_specs=q_spec, check_vma=False)
    return f(*operands)


# one-shot gather cap: [T, C, 2, Hkv, D] materializes T*C*2*Hkv*D
# elements; past this many BYTES the chunked online-softmax path runs
# instead (bench shapes at GPT-2s blew HBM: 3.2 GB gather -> 18.5 G
# peak on a 15.75 G v5e)
_ONE_SHOT_GATHER_BYTES = 512 * 1024 * 1024


def _paged_attention(kv_layer, q, batch: RaggedBatch, block_size: int,
                     max_blocks_per_seq: int, scale: float, slopes=None,
                     layer=None, window=None):
    """Per-token attention over the owning sequence's context
    (reference kernel: blocked_flash / flash_attn_by_atoms).

    q: [T, H, D] → out [T, H, D].  XLA formulation: gather each token's
    block table (bounded by max_blocks_per_seq), mask by position.  When
    the full-context gather would exceed ``_ONE_SHOT_GATHER_BYTES`` the
    computation streams one KV block at a time with an online-softmax
    accumulator instead (memory ∝ T·block_size, not T·context).  The
    Pallas streaming variant (``_paged_attention_pallas``) drops in
    behind the same signature (``InferenceEngine.attn_impl`` says which
    one an engine runs).  ``window``: a window layer's window; both XLA
    formulations mask what lies behind it (the kernel skips it).
    """
    T, H, D = q.shape
    data, scales = _kv_parts(kv_layer)
    Hkv = data.shape[3]
    C = max_blocks_per_seq * block_size
    gather_bytes = T * C * 2 * Hkv * D * data.dtype.itemsize
    if gather_bytes > _ONE_SHOT_GATHER_BYTES:
        return _paged_attention_chunked(kv_layer, q, batch, block_size,
                                        max_blocks_per_seq, scale,
                                        slopes=slopes, layer=layer,
                                        window=window)
    rep = H // Hkv

    tables = _layer_tables(
        batch.block_tables[batch.seq_slot, :max_blocks_per_seq],
        _layer_of(kv_layer, layer))                                # [T, nb]
    ctx = data[tables]                # [T, nb, bs, 2, Hkv, D]
    ctx = ctx.reshape(T, C, 2, Hkv, D)
    k_ctx, v_ctx = ctx[:, :, 0], ctx[:, :, 1]                     # [T, C, Hkv, D]
    if scales is not None:
        sctx = _block_scales(scales[tables]).reshape(T, C, 2, Hkv)
        k_ctx = _dequant_ctx(k_ctx, sctx[:, :, 0], q.dtype)
        v_ctx = _dequant_ctx(v_ctx, sctx[:, :, 1], q.dtype)

    qg = q.reshape(T, Hkv, rep, D)
    s = jnp.einsum("thrd,tchd->thrc", qg, k_ctx).astype(jnp.float32) * scale
    cols = jnp.arange(C)[None, :]                                  # [1, C]
    if slopes is not None:      # ALiBi: slope_h * absolute key position
        s = s + (slopes.reshape(Hkv, rep)[None, :, :, None]
                 * cols[:, None, None, :].astype(jnp.float32))
    valid = cols <= batch.positions[:, None]                       # [T, C]
    if window is not None:
        valid &= cols > batch.positions[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("thrc,tchd->thrd", p, v_ctx)
    return o.reshape(T, H, D)


def _paged_attention_chunked(kv_layer, q, batch: RaggedBatch,
                             block_size: int, max_blocks_per_seq: int,
                             scale: float, slopes=None, layer=None,
                             window=None):
    """Streaming XLA paged attention: scan over the block-table columns,
    gathering ONE context block per step ([T, bs, 2, Hkv, D]) and folding
    it into an online-softmax accumulator — same numerics as the
    one-shot softmax, peak memory ∝ T·block_size."""
    T, H, D = q.shape
    data, scales = _kv_parts(kv_layer)
    Hkv = data.shape[3]
    rep = H // Hkv
    bs = block_size

    tables = _layer_tables(
        batch.block_tables[batch.seq_slot, :max_blocks_per_seq],
        _layer_of(kv_layer, layer))                                # [T, nb]
    qg = q.reshape(T, Hkv, rep, D)
    offs = jnp.arange(bs)

    def fold(carry, j):
        m, l, acc = carry
        blk = tables[:, j]                          # [T]
        ctx = data[blk]                             # [T, bs, 2, Hkv, D]
        k, v = ctx[:, :, 0], ctx[:, :, 1]           # [T, bs, Hkv, D]
        if scales is not None:
            sc = _block_scales(scales[blk])         # [T, bs, 2, Hkv]
            k = _dequant_ctx(k, sc[:, :, 0], q.dtype)
            v = _dequant_ctx(v, sc[:, :, 1], q.dtype)
        s = jnp.einsum("thrd,tbhd->thrb", qg, k).astype(jnp.float32) * scale
        cols = j * bs + offs[None, :]               # [1, bs]
        if slopes is not None:
            s = s + (slopes.reshape(Hkv, rep)[None, :, :, None]
                     * cols[:, None, None, :].astype(jnp.float32))
        valid = cols <= batch.positions[:, None]    # [T, bs]
        if window is not None:
            valid &= cols > batch.positions[:, None] - window
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        w = jnp.exp(m - m_new)
        l = l * w + p.sum(axis=-1)
        pv = jnp.einsum("thrb,tbhd->thrd", p.astype(q.dtype), v)
        acc = acc * w[..., None] + pv.astype(jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((T, Hkv, rep), -jnp.inf, jnp.float32),
            jnp.zeros((T, Hkv, rep), jnp.float32),
            jnp.zeros((T, Hkv, rep, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        fold, init, jnp.arange(max_blocks_per_seq, dtype=jnp.int32))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(T, H, D).astype(q.dtype)




def _stream_layer(stream, li, dt, mixed_gemm: bool = False):
    """Fetch layer ``li``'s weights from the NVMe store (host callback)
    and dequantize any streamed quantized payloads on device — or, with
    ``mixed_gemm``, keep row-wise int8 payloads quantized for the
    VMEM-dequant kernel (the weight stays int8-sized from NVMe through
    HBM into the MXU feed)."""
    rec = stream.fetch_layer(li)
    lp = {k: (dict(v) if isinstance(v, dict) else v)
          for k, v in rec["dense"].items()}
    if "quant" in rec:
        from ..ops.quant import (QuantizedTensor, dequantize_any,
                                 is_mixed_gemm_layout)
        from .quantization import DENSE_ONLY_GROUPS
        for gname, grp in rec["quant"].items():
            g = dict(lp.get(gname, {}))
            for name, arrs in grp.items():
                bits, shp, odt, layout = stream.qmeta[gname][name]
                qt = QuantizedTensor(arrs["data"], arrs["scale"],
                                     arrs.get("zero"), bits, shp, odt,
                                     layout=layout)
                if mixed_gemm and gname not in DENSE_ONLY_GROUPS \
                        and is_mixed_gemm_layout(qt):
                    g[name] = qt
                else:
                    g[name] = dequantize_any(qt, dt)
            lp[gname] = g
    return lp


def _mm(x, w, dt, contract_dims: int = 1):
    """``x @ w`` where ``w`` is dense — or a row-wise QuantizedTensor,
    routed through the mixed-input VMEM-dequant kernel
    (ops/mixed_gemm.py; reference: cuda_linear fp6_linear.cu).

    Always returns ``dt``: a wider activation (e.g. the attention output
    under an f32 KV cache with bf16 weights) must not promote the
    residual stream past the serving dtype — the scan carry is ``dt``,
    and the mixed-GEMM branch emits ``dt`` unconditionally."""
    from ..ops.quant import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        from ..ops.mixed_gemm import mixed_matmul
        return mixed_matmul(x, w, contract_dims=contract_dims,
                            out_dtype=dt)
    wshape = w.shape
    K = int(np.prod(wshape[:contract_dims]))
    y = (x.reshape(-1, K) @ w.reshape(K, -1).astype(dt)).astype(dt)
    return y.reshape(*x.shape[:-1], *wshape[contract_dims:])


# the projections of a block's ``attn`` group (``full``: a "full" layer's
# in a model whose mixers are stacked by kind) whose columns are heads:
# the model keeps them ``[L, d, H, D]`` (and ``wo``, whose rows are,
# ``[L, H, D, d]``)
_HEAD_PROJECTIONS = ("wq", "wk", "wv", "wg")


def fold_projection(path, w, wo: bool = True):
    """The rule of :func:`fold_projections` for one leaf at ``path`` of
    the tree: a stacked head projection ``[L, d, H, D]`` as ``[L, d,
    H*D]``, attention's ``wo`` ``[L, H, D, d]`` as ``[L, H*D, d]``, every
    other leaf itself.  A QuantizedTensor head projection is folded in
    its own layout (row-wise scales go by ``(layer, row)`` and the grouped
    form runs over the flat layer: the same numbers either way).  ``wo``'s
    row-wise scales go by HEAD, so a ``wo`` that is or will be quantized
    stays ``[L, H, D, d]`` (``wo=False``: an engine that quantizes its
    weights), which ``_out_proj`` takes as well; a layer of it is
    dequantized into a temporary a step in any case."""
    from ..ops.quant import QuantizedTensor
    group, name = [getattr(p, "key", None) for p in path[-2:]] \
        if len(path) >= 2 else (None, None)
    if group not in ("attn", "full") or len(w.shape) != 4:
        return w
    quantized = isinstance(w, QuantizedTensor)
    L_, a, b, c = w.shape
    if name == "wo":
        return w.reshape(L_, a * b, c) if wo and not quantized else w
    if name not in _HEAD_PROJECTIONS:
        return w
    shape = (L_, a, b * c)
    if not quantized:
        return w.reshape(shape)
    data, scale = w.data, w.scale
    if tuple(data.shape) == tuple(w.shape):         # row-wise int8
        data, scale = data.reshape(shape), scale.reshape(L_, a, 1)
    elif data.ndim == 4:            # packed row-wise fp6/fp12: ``D`` packed
        data = data.reshape(L_, a, -1)
    return QuantizedTensor(data, scale, w.zero, w.bits, shape, w.dtype,
                           layout=w.layout)


def fold_projections(tree):
    """The tree the engine serves, from the model's parameter tree (or
    the quantized tree beside it): every stacked projection of an
    ``attn`` group (``blocks`` and ``dense_blocks`` alike) as the matrix
    its product reads, ``[L, d, H*D]`` (``wq``, ``wk``, ``wv``, the gate)
    and ``[L, H*D, d]`` (``wo``).  On the chip a stacked weight of rank
    four is tiled over its two minor dimensions and a step does not read
    a layer of it where it lies: it cuts the layer out into a temporary
    first, every byte, a layer a step; of rank three the product reads
    the stack, as the MLP's do (PERF.md section 6, PR 45).  A leaf that
    is folded already no longer has the rank that folds: the fold
    applied twice is the fold applied once."""
    from ..ops.quant import QuantizedTensor
    return jax.tree_util.tree_map_with_path(
        fold_projection, tree,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def _head_proj(h, w, dt, heads: int, head_dim: int):
    """``h @ w`` for a head projection as the engine holds it, one
    layer's ``[d, heads * head_dim]`` (dense or quantized) → ``[T, heads,
    head_dim]``: the activation is what is reshaped.  A weight of the
    model's own ``[d, H, D]`` form is refused: the product would not read
    it where it lies in the stack (``fold_projections``)."""
    assert len(w.shape) == 2, (
        "a head projection reaches the serving forward unfolded "
        f"{tuple(w.shape)}: pass the tree through fold_projections")
    # the product's rows exist before they are cut into heads.  Left to
    # choose the product's layout by the reshape behind it, the TPU's
    # compiler makes the reshape a bitcast by computing the product
    # transposed, and transposes the weight for it: every byte of it, a
    # layer a step (PERF.md section 6, PR 45)
    y = jax.lax.optimization_barrier(_mm(h, w, dt))
    return y.reshape(-1, heads, head_dim)


def _out_proj(o, wo, dt):
    """``o [T, H, D]`` through attention's output projection as the
    engine holds it, one layer's ``[H*D, d]`` (``[H, D, d]`` where it is
    quantized: ``fold_projection``) → ``[T, d]``."""
    return _mm(o.reshape(o.shape[0], -1), wo, dt,
               contract_dims=len(wo.shape) - 1)


def _qkv_proj(cfg, ap, h, dt, cos, sin, positions, kind: str = "full"):
    """The qkv projection + biases + rotary of the served step's
    attention.  ``kind``: the layer's attention
    kind, which says whether it takes the rotary embedding."""
    if cfg.attn_in_scale != 1.0:
        h = h * jnp.asarray(cfg.attn_in_scale, dt)
    q = _head_proj(h, ap["wq"], dt, cfg.num_heads, cfg.head_dim)
    k = _head_proj(h, ap["wk"], dt, cfg.num_kv_heads, cfg.head_dim)
    v = _head_proj(h, ap["wv"], dt, cfg.num_kv_heads, cfg.head_dim)
    if cfg.key_scale != 1.0:
        k = k * jnp.asarray(cfg.key_scale, dt)
    if cfg.attn_bias:
        q = q + ap["bq"].astype(dt)
        k = k + ap["bk"].astype(dt)
        v = v + ap["bv"].astype(dt)
    if cfg.qk_norm:
        q = _qk_norm(cfg, ap["q_norm"], q)
        k = _qk_norm(cfg, ap["k_norm"], k)
    if cfg.rope_on(kind):
        # apply_rope expects [B, S, H, D]; B=1 with per-token positions
        q = L.apply_rope(q[None], cos, sin, positions=positions[None])[0]
        k = L.apply_rope(k[None], cos, sin, positions=positions[None])[0]
    return q, k, v


def _latent_runs(batch: RaggedBatch):
    """A step's runs as a latent layer reads them, for a model that has
    no recurrent layer beside it (``_ssm_runs`` gives the same keys and
    the state's): from ``batch.rec``, once a step."""
    rec = batch.rec
    one = rec.run_len == 1
    return dict(S=rec.run_len.shape[0], one=one, chunks=rec.chunks,
                last=jnp.maximum(batch.logits_idx, 0),
                row_one=one[batch.seq_slot])


def _ssm_runs(batch: RaggedBatch, width: int):
    """A step's runs as the mixer reads them, from ``batch.rec``: once a
    step, outside the layer scan.  Per slot: the flat rows of its run's
    first and last token, whether the run is there, is one token, is a
    replay, starts at position 0, and where in the slot's tail (of
    ``width`` entries) the input before the run sits: the newest, or for
    a replayed row, whose own input is the newest, the one before.  Per
    row (``row_*``): its slot's."""
    rec = batch.rec
    last = jnp.maximum(batch.logits_idx, 0)                    # [S]
    per_slot = dict(
        first=last - rec.run_len + 1, one=rec.run_len == 1,
        fresh=(batch.context_lens == rec.run_len) & (rec.run_len > 0),
        offset=width - 1 - rec.replay.astype(jnp.int32))
    return dict(
        per_slot, S=rec.run_len.shape[0], last=last,
        has_run=rec.run_len > 0, replay=rec.replay, chunks=rec.chunks,
        **{"row_" + k: v[batch.seq_slot] for k, v in per_slot.items()})


def _ssm_mixer(cfg, mp, u, rec_state, li, batch: RaggedBatch, runs, dt,
               kernel: bool = False):
    """A "hybrid" or "mamba" layer's Mamba-2 mixer over a step's flat
    rows.

    u: [T, dm], the normed input times its multiplier.  ``rec_state``:
    ``(ssm [L, S+1, H, P, N], conv [L, S+1, W, C])``, the engine's state
    rows of every layer that holds a state; ``li``: the layer's rank
    among them, whose rows it reads and writes in place.
    A one-token run advances its slot's state by the dense update
    (``ssm_update``: with ``kernel`` the Pallas kernel over the stack,
    one read and one write a row; else XLA's over the layer cut out of
    it, which reads a row twice); a longer run goes through the chunked form
    (``ssm_scan``) from the slot's state, or from zeros where it starts
    at position 0, and leaves its last state in the slot (with
    ``kernel`` ONE Pallas kernel over the chunks the step's table holds,
    the state read from and left in the stack in place; else XLA's
    ``chunk_scan`` over every chunk of the table, the state rows cut out
    and written back around it); the convolution reaches into the
    slot's tail (``ssm_conv``).
    → (y [T, dm], rec_state)."""
    from ..ops import ssm as M

    dims = cfg.ssm_dims
    ssm, conv = rec_state
    S, T = runs["S"], u.shape[0]
    with jax.named_scope("ssm_in"):
        z, xbc, dt_raw = M.split_in_proj(_mm(u, mp["w_in"], dt), dims,
                                         cfg.ssm_col_scales)
    with jax.named_scope("ssm_conv"):
        tail = jax.lax.dynamic_index_in_dim(conv, li, keepdims=False)
        act = M.conv_rows(
            xbc, tail[:S], batch.seq_slot, runs["row_first"],
            runs["row_offset"], runs["row_fresh"], mp["conv_w"],
            mp["conv_b"]).astype(dt)
        x, b, c = M.split_xbc(act, dims)
        new_tail = M.conv_tails(
            xbc, tail[:S], runs["last"], runs["first"], runs["offset"],
            runs["fresh"], runs["has_run"])
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[None], (li, 0, 0, 0))
        dts, a = M.discretise(dt_raw, mp)
    with jax.named_scope("ssm_update"):
        at = runs["last"]
        # (XLA's branch as it stood, operation for operation: the CPU's
        # lowered step is hashed, tests/test_ling.py)
        if kernel:
            y_one, ssm = M.state_update_in_place(
                ssm, li, x[at], b[at], c[at], dts[at], a, mp["D"],
                runs["one"], runs["replay"], runs["fresh"], dims)
        else:
            pool = jax.lax.dynamic_index_in_dim(ssm, li, keepdims=False)
            y_one, new = M.state_update(
                pool[:S], x[at], b[at], c[at], dts[at], a, mp["D"],
                runs["one"], runs["replay"], runs["fresh"], dims)
            ssm = jax.lax.dynamic_update_slice(ssm, new[None],
                                               (li, 0, 0, 0, 0))
    with jax.named_scope("ssm_scan"):
        ch = runs["chunks"]
        if kernel:
            # ONE kernel over the chunks that are there: their rows by
            # its own DMAs out of the convolution's result as it lies, a
            # run's state read from and left in its slot's row of the
            # stack, no tile of Q x Q x heads in HBM
            y, ssm = M.chunk_scan_in_place(
                ssm, li, act, dts, a, mp["D"], ch,
                runs["fresh"][jnp.minimum(ch[:, 2], S - 1)], dims)
        else:
            # (XLA's branch as it stood, operation for operation: the
            # CPU's lowered step is hashed, tests/test_falcon_h1.py)
            start, n, slot, first, lastc = (ch[:, i] for i in range(5))
            Q = dims.chunk
            q = jnp.arange(Q)[None, :]
            there = q < n[:, None]                                   # [NC, Q]
            rows = jnp.minimum(start[:, None] + q, T - 1)
            # a chunk's first state is cut out of the stack where it lies
            # and its last written back there, one row of 2 MiB at a time:
            # a gather over the layer would copy the layer first
            row = (1, 1) + ssm.shape[2:]
            init = jnp.concatenate([jax.lax.dynamic_slice(
                ssm, (li, slot[i], 0, 0, 0), row)[0]
                for i in range(ch.shape[0])])
            fresh = runs["fresh"][jnp.minimum(slot, S - 1)]
            # about half of a chat mix's steps hold no run of several tokens:
            # they skip the chunked form's products (the reads and writes of
            # the 2 MiB rows around it go to the trash row and stay)
            shape = (ch.shape[0], Q, dims.heads)
            y_run, left = jax.lax.cond(
                jnp.any(n > 0),
                lambda: M.chunk_scan(
                    x[rows], b[rows], c[rows],
                    jnp.where(there[..., None], dts[rows], 0.0), a, mp["D"],
                    first.astype(bool),
                    jnp.where(fresh[:, None, None, None], 0,
                              init.astype(jnp.float32)), dims),
                lambda: (jnp.zeros(shape + (dims.head_dim,), jnp.float32),
                         jnp.zeros(init.shape, jnp.float32)))
            # a run's last chunk leaves its state in the slot; the others'
            # (and the chunks that are not there) go to the trash row
            to = jnp.where(lastc.astype(bool), slot, S)
            left = left.astype(ssm.dtype)
            for i in range(ch.shape[0]):
                ssm = jax.lax.dynamic_update_slice(
                    ssm, left[i][None, None], (li, to[i], 0, 0, 0))
            y = jnp.zeros((T,) + y_run.shape[2:], jnp.float32).at[
                jnp.where(there, rows, T).reshape(-1)].set(
                y_run.reshape((-1,) + y_run.shape[2:]), mode="drop")
        y = jnp.where(runs["row_one"][:, None, None],
                      y_one[batch.seq_slot], y)
    with jax.named_scope("ssm_out"):
        y = M.gated_norm(y.reshape(T, dims.d_ssm), z, mp["norm"], dims,
                         cfg.eps).astype(dt)
        return _mm(y, mp["w_out"], dt), (ssm, conv)


def _chunk_rows(runs, T: int, Q: int):
    """The flat rows of a step's chunks (``RecBatch.chunks``) → (rows
    [NC, Q] clipped into the step, there [NC, Q], and the chunks'
    columns: rows, slot, first of its run, last of its run)."""
    ch = runs["chunks"]
    start, n, slot, first, lastc = (ch[:, i] for i in range(5))
    q = jnp.arange(Q)[None, :]
    return (jnp.minimum(start[:, None] + q, T - 1), q < n[:, None], n,
            slot, first.astype(bool), lastc.astype(bool))


def _scatter_chunks(y_run, rows, there, T: int):
    """The chunks' rows back in the step's flat order, zeros elsewhere."""
    return jnp.zeros((T,) + y_run.shape[2:], y_run.dtype).at[
        jnp.where(there, rows, T).reshape(-1)].set(
        y_run.reshape((-1,) + y_run.shape[2:]), mode="drop")


def _kda_mixer(cfg, mp, h, rec_state, li, batch: RaggedBatch, runs, dt,
               kernel: bool = False):
    """A "kda" layer's mixer over a step's flat rows (``ops/kda.py``).

    h: [T, dm], the normed input.  ``rec_state``: ``(state [L, S+1, H,
    K, V], conv [L, S+1, W, C])``, the engine's state rows of the layers
    that hold one; ``li``: this layer's rank among them.  A one-token
    run advances its slot's state by the dense update (``kda_update``,
    by the Pallas kernel or by XLA as ``_ssm_mixer``'s);
    a longer run goes through the chunked form (``kda_chunk``) from the
    slot's state, or from zeros where it starts at position 0, and
    leaves its last state in the slot; the convolution reaches into the
    slot's tail (``kda_conv``).  → (y [T, dm], rec_state)."""
    from ..ops import kda as K
    from ..ops.ssm import conv_rows, conv_tails

    dims = cfg.kda_dims
    ssm, conv = rec_state
    S, T = runs["S"], h.shape[0]
    with jax.named_scope("kda_in"):
        xc = _mm(h, mp["w_qkv"], dt)
        a_raw, b_raw = _mm(h, mp["w_f"], dt), _mm(h, mp["w_b"], dt)
        gate_raw = _mm(h, mp["w_g"], dt)
    with jax.named_scope("kda_conv"):
        tail = jax.lax.dynamic_index_in_dim(conv, li, keepdims=False)
        q, k, v = K.split_qkv(conv_rows(
            xc, tail[:S], batch.seq_slot, runs["row_first"],
            runs["row_offset"], runs["row_fresh"], mp["conv_w"],
            jnp.zeros((xc.shape[-1],), jnp.float32)).astype(dt), dims,
            cfg.eps)
        new_tail = conv_tails(
            xc, tail[:S], runs["last"], runs["first"], runs["offset"],
            runs["fresh"], runs["has_run"])
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[None], (li, 0, 0, 0))
    with jax.named_scope("kda_gate"):
        g, beta = K.gates(a_raw, b_raw, mp, dims)
    with jax.named_scope("kda_update"):
        at = runs["last"]
        if kernel:
            o_one, ssm = K.state_update_in_place(
                ssm, li, q[at], k[at], v[at], g[at], beta[at], runs["one"],
                runs["replay"], runs["fresh"])
        else:
            pool = jax.lax.dynamic_index_in_dim(ssm, li, keepdims=False)
            o_one, new = K.state_update(
                pool[:S], q[at], k[at], v[at], g[at], beta[at], runs["one"],
                runs["replay"], runs["fresh"])
            ssm = jax.lax.dynamic_update_slice(ssm, new[None],
                                               (li, 0, 0, 0, 0))
    with jax.named_scope("kda_chunk"):
        rows, there, n, slot, first, lastc = _chunk_rows(runs, T, dims.chunk)
        NC = n.shape[0]
        # a chunk's first state is cut out of the stack where it lies
        # and its last written back there, a row at a time (``_ssm_mixer``)
        row = (1, 1) + ssm.shape[2:]
        init = jnp.concatenate([jax.lax.dynamic_slice(
            ssm, (li, slot[i], 0, 0, 0), row)[0] for i in range(NC)])
        fresh = runs["fresh"][jnp.minimum(slot, S - 1)]
        o_run, left = jax.lax.cond(
            jnp.any(n > 0),
            lambda: K.chunk_rule(
                q[rows], k[rows], v[rows],
                jnp.where(there[..., None, None], g[rows], 0.0),
                jnp.where(there[..., None], beta[rows], 0.0), first,
                jnp.where(fresh[:, None, None, None], 0,
                          init.astype(jnp.float32)), dims),
            lambda: (jnp.zeros((NC, dims.chunk, dims.heads,
                                dims.value_dim), jnp.float32),
                     jnp.zeros(init.shape, jnp.float32)))
        # a run's last chunk leaves its state in the slot; the others'
        # (and the chunks that are not there) go to the trash row
        to = jnp.where(lastc, slot, S)
        left = left.astype(ssm.dtype)
        for i in range(NC):
            ssm = jax.lax.dynamic_update_slice(
                ssm, left[i][None, None], (li, to[i], 0, 0, 0))
        o = jnp.where(runs["row_one"][:, None, None], o_one[batch.seq_slot],
                      _scatter_chunks(o_run, rows, there, T))
    with jax.named_scope("kda_gate"):
        o = K.gated_norm(o, gate_raw, mp["norm"], cfg.eps).astype(dt)
    with jax.named_scope("kda_out"):
        return _mm(o, mp["w_o"], dt), (ssm, conv)


# cached blocks one pass of the latent attention's loop reads: the
# one-token rows', a group a slot, and the chunks' of longer runs
_LATENT_BLOCKS_ONE = 8
_LATENT_BLOCKS_RUN = 4


def _latent_attend_xla(cfg, qf, pool, layer, batch: RaggedBatch, runs,
                       max_blocks_per_seq: int):
    """The XLA formulation of a step's latent attention
    (``ops/mla.py`` ``latent_attend``, twice): the one-token rows as one
    group a slot, the longer runs by their chunks.  qf: [T, H, row]
    folded queries → [T, H, kv_rank] float32."""
    from ..ops import mla as A

    dims = cfg.mla_dims
    S, T = runs["S"], qf.shape[0]
    tables = _layer_tables(batch.block_tables[:, :max_blocks_per_seq],
                           layer)                               # [S, nb]
    qpos = jnp.where(runs["one"], batch.context_lens - 1, -1)
    o_one = A.latent_attend(pool, qf[runs["last"]][:, None],
                            qpos[:, None], tables[:S], dims,
                            _LATENT_BLOCKS_ONE)[:, 0]
    rows, there, n, slot, _, _ = _chunk_rows(runs, T, cfg.kda_chunk)
    o_run = jax.lax.cond(
        jnp.any(n > 0),
        lambda: A.latent_attend(
            pool, qf[rows], jnp.where(there, batch.positions[rows], -1),
            tables[jnp.minimum(slot, S - 1)], dims, _LATENT_BLOCKS_RUN),
        lambda: jnp.zeros(rows.shape + (dims.heads, dims.kv_rank),
                          jnp.float32))
    return jnp.where(runs["row_one"][:, None, None], o_one[batch.seq_slot],
                     _scatter_chunks(o_run, rows, there, T))


def _latent_attention(cfg, ap, h, pool, layer, batch: RaggedBatch, runs,
                      cos, sin, dt, block_size: int, max_blocks_per_seq: int,
                      tiles=None):
    """An "mla" layer's attention over a step's flat rows
    (``ops/mla.py``): every row's ``[c | k_r]`` written into the latent
    pool by block table (``latent_write``; a replayed row writes its row
    again), then all the query heads over the cached rows of the row's
    sequence, ``W_kvb`` folded into the query and the output
    (``latent_attn``): by the Pallas kernel over ``tiles``
    (``_latent_tiles`` of the step; its third list's runs in the
    expanded form, per-head keys and values built in VMEM) where the
    caller has them, else by
    the XLA formulation, the one-token rows as one group a slot, the
    longer runs by their chunks.  ``pool``: the latent pool ``[L * rows,
    bs, row]``, which holds the layer where ``layer`` says
    (``_layer_of``).  → (o [T, dm], pool)."""
    from ..ops import mla as A

    dims = cfg.mla_dims
    T = h.shape[0]
    base, nrows = layer
    def mm(x, w):
        y = _mm(x, w, dt)
        # a product whose rows are cut into heads behind it exists as
        # rows first, as ``_head_proj``'s (the query latent's ``W_qb``)
        return jax.lax.optimization_barrier(y) if dims.q_rank else y

    with jax.named_scope("latent_in"):
        q_n, q_r, row = A.project(ap, h, cos, sin, batch.positions, dims,
                                  cfg.eps, mm)
        # (where the step has runs long enough for the expanded form the
        # folded query is made for the rows that take that form)
        by_run = tiles is not None and tiles.wide is not None
        if not by_run:
            qf = A.fold_query(ap, q_n, q_r, dims)           # [T, H, row]
        if cfg.mla_gate == "head":
            gate = _mm(h, ap["wg"], dt)
    with jax.named_scope("latent_write"):
        blk = batch.block_tables[batch.seq_slot,
                                 batch.positions // block_size]
        blk = jnp.where(batch.token_valid, blk + base, base + nrows - 1)
        pool = A.latent_write(pool, row, blk, batch.positions % block_size)
    with jax.named_scope("latent_attn"):
        if by_run:
            # each run by the call its length takes: the runs long enough
            # to pay for ``c W_kvb`` expanded (no third list at a row
            # count that holds no such run)
            o = A.latent_attend_runs(ap, pool, q_n, q_r, tiles, dims, layer,
                                     dt)                    # [T, H, V]
        else:
            if tiles is not None:
                o = A.latent_attend_tiles(pool, qf, tiles, dims, layer)
            else:
                o = _latent_attend_xla(cfg, qf, pool, layer, batch, runs,
                                       max_blocks_per_seq)
            o = A.unfold_output(ap, o, dims, dt)            # [T, H, V]
    with jax.named_scope("latent_out"):
        if cfg.mla_gate == "head":
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                dt)[..., None]
        return _mm(o.reshape(T, -1), ap["wo"], dt,
                   contract_dims=len(ap["wo"].shape) - 1), pool


def _dense_weight(w) -> bool:
    """Whether ``w`` is a plain array (mixed-GEMM QuantizedTensor
    weights keep their VMEM-dequant kernel path and never route through
    the decomposed collectives)."""
    from ..ops.quant import QuantizedTensor
    return not isinstance(w, QuantizedTensor)


def _ffn(cfg, lp, h, dt, act, comm: Optional[ServingComm] = None,
         valid=None, sharded: bool = False, experts=None,
         routing: bool = False):
    """Shared MLP / MoE branch of a serving layer → ``(d, moe_stats)``,
    ``moe_stats`` None for a dense layer (a dense model's, or a leading
    dense layer of a sparse one: the layer that has ``lp["mlp"]``).
    With ``routing`` an expert layer's is ``(moe_stats, the experts each
    row took [T, top_k])``.

    Sparse experts are served dropless, by construction and with no
    option (``parallel/moe.py`` ``moe_serve``): ``valid`` marks the real
    rows of the step's bucket, the rest are routed nowhere.  The grouped
    Pallas kernel runs where it can (a TPU, weights on one device;
    ``sharded`` says they are not), ``jax.lax.ragged_dot`` elsewhere.
    ``experts``: ``(all layers' stacked expert weights, this layer's
    index)`` where the layer scan kept them out of its scanned inputs
    (``ragged_forward``); else the layer's own are ``lp["experts"]``.

    With ``comm`` (TP serving, comm_overlap on), the down-projection —
    the layer's one row-parallel GEMM, whose partial-sum all-reduce
    GSPMD would otherwise run serially after it — goes through the
    T3-style tile-decomposed matmul+allreduce instead
    (comm/overlap.py; bitwise-identical on the default exact rung)."""
    if "mlp" not in lp:
        from ..models.transformer import _shared_expert
        from ..parallel import moe as M

        stack, layer = experts or (lp["experts"], None)
        d, *stats = M.moe_serve(
            lp["gate"], stack, h, valid, top_k=cfg.moe_top_k,
            activation=act, gated=cfg.gated_mlp,
            norm_topk=cfg.moe_norm_topk, layer=layer,
            kernel=jax.default_backend() == "tpu" and not sharded,
            score=cfg.moe_score, route_scale=cfg.moe_route_scale,
            with_ids=routing, held_groups=cfg.held_groups,
            **moe_share(cfg))
        stats = tuple(stats) if routing else stats[0]
        if "shared" in lp:       # the dense expert every token takes
            with jax.named_scope("moe_shared"):
                d = d + _shared_expert(lp["shared"], h, act,
                                       cfg.gated_mlp)
        return d, stats
    mp = lp["mlp"]
    u = _mm(h, mp["wi"], dt)
    if cfg.mlp_bias:
        u = u + mp["bi"].astype(dt)
    if cfg.gated_mlp:
        g = _mm(h, mp["wg"], dt)
        if cfg.mlp_gate_scale != 1.0:
            g = g * jnp.asarray(cfg.mlp_gate_scale, dt)
        u = act(g) * u
    else:
        u = act(u)
    wo = mp["wo"]
    if comm is not None and comm.downproj and _dense_weight(wo):
        d = shard_matmul_allreduce(u, wo, comm, dt)
    else:
        d = _mm(u, wo, dt)
    if cfg.mlp_bias:
        d = d + mp["bo"].astype(dt)
    if cfg.mlp_out_scale != 1.0:
        d = d * jnp.asarray(cfg.mlp_out_scale, dt)
    return d, None


def ragged_forward(cfg: TransformerConfig, params, kv, batch: RaggedBatch,
                   block_size: int, max_blocks_per_seq: int,
                   rng: Optional[jax.Array] = None,
                   attn_impl: str = "xla",
                   quant=None,
                   shard_mesh=None,
                   stream=None,
                   mixed_gemm: bool = False,
                   comm: Optional[ServingComm] = None,
                   with_moe_stats: bool = False,
                   with_routing: bool = False,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (last_token_logits [max_seqs, vocab], new_kv), and with
    ``with_moe_stats`` (sparse-expert models) a third: ``[3] i32``, the
    step's expert assignments summed over the layers, 1000 x its worst
    layer's fullest-expert-over-mean and the experts that took a row,
    summed over the layers (``moe_serve``).  With ``with_routing``
    (sparse-expert models) a last: ``[expert layers, T, top_k] i32``,
    the experts each row of the step took (a row that pads the step's
    bucket: ``num_experts``), for a consumer that has to know the
    router's choice (a comparison that follows it, a router's analysis).

    ``kv``: [L, blocks, bs, 2, Hkv, D].  Rows of the logits output whose
    ``batch.logits_idx`` is -1 are garbage (callers mask by it).

    The layers run as ``cfg.layer_plan`` says (``models/transformer.py``
    ``apply`` reads the same): the leading dense layers one by one, ONE
    scan over the whole periods of ``cfg.layer_pattern`` whose body
    holds a period's layers, each of a static kind (a window layer and
    a full layer call different kernels), then a last period cut short.
    A model of one block type is a period of one layer.

    Every path is position-absolute: a batch whose tokens START at a
    nonzero context offset (chunked SplitFuse prefill — and, same
    mechanism, a prefill resuming after a prefix-cache hit aliased the
    leading blocks) needs no special handling: rope/learned positions
    index ``batch.positions``, KV writes land at
    ``block_tables[pos // bs], pos % bs``, and attention masks by
    absolute key position ≤ query position over whatever the block
    table references.
    ``attn_impl``: "xla" (gather) | "pallas" (streaming kernel; for an
    "mla" layer the latent kernel of ``ops/mla.py``).
    ``quant``: ZeRO-Inference weight-quant tree (inference/quantization
    ``quantize_model_params``) — one layer is dequantized at a time
    inside the scan body, so dense weights never all coexist in HBM.
    The cache rides the layer scan as a carry that every layer updates
    in place: the stack is viewed as ``[L * rows, ...]`` and layer
    ``li`` addresses its rows by block ids moved by ``li * rows``
    (``_layer_of``), so no layer is ever sliced out of the stack or
    written back into it.
    ``stream``: an :class:`~.weight_stream.NVMeWeightStore` — the layer
    scan fetches each layer's (possibly quantized) weights from NVMe via
    ``io_callback`` so HBM holds one layer's weights at a time
    (reference: partitioned_param_swapper.py:290 / ZeRO-Inference NVMe).
    ``comm``: a resolved :class:`~..comm.overlap.ServingComm` plan — the
    MLP down-projection's all-reduce and/or the unembed's logits gather
    run tile-decomposed (T3) and optionally quantized (EQuARX) instead
    of as GSPMD's serial collectives (docs/SERVING.md "Overlapped &
    quantized collectives").
    """
    if (quant is not None or stream is not None) and not cfg.plain_stack:
        raise NotImplementedError(
            "weight quantization and the NVMe weight stream serve a model "
            "of one block type (TransformerConfig.plain_stack)")
    # a model with recurrent layers: the cache is the paged pool AND the
    # state rows by slot (``KVCacheConfig.cache_zeros``); both ride the
    # layer scan as carries that every layer updates in place
    rec = runs = None
    # the one-token state update is the Pallas kernel where the experts'
    # is (``_ffn``): a TPU, the state rows on one device
    state_kernel = jax.default_backend() == "tpu" and shard_mesh is None
    if cfg.has_ssm:
        rec = (kv["ssm"], kv["conv"])
        kv = kv["kv"]
        runs = _ssm_runs(batch, cfg.kda_conv if "kda" in cfg.mixer_stacks
                         else cfg.ssm_conv)
    elif "mla" in cfg.mixer_stacks:
        runs = _latent_runs(batch)
    if quant is not None:
        from .quantization import merge_layer
        from ..ops.quant import dequantize_any
    if quant is not None and "embed" in quant:
        embed_tab = {"table": dequantize_any(quant["embed"]["table"])}
        dt = embed_tab["table"].dtype
    else:
        embed_tab = params["embed"]
        dt = embed_tab["table"].dtype
    norm = _norm(cfg)
    act = L.ACTIVATIONS[cfg.activation]
    scale = (cfg.attn_scale if cfg.attn_scale is not None
             else 1.0 / (cfg.head_dim ** 0.5))
    # the chips a tensor mesh splits the kv heads over: a pool allocated
    # for the kernel holds each chip's own heads filled up (``_fill_heads``)
    head_groups = 1
    if shard_mesh is not None:
        from ..comm.mesh import TENSOR_AXIS
        head_groups = shard_mesh.shape[TENSOR_AXIS]
    pattern = cfg.layer_pattern
    P = len(pattern)
    lead, periods, tail = cfg.layer_plan

    x = L.embed(embed_tab, batch.token_ids).astype(dt)             # [T, dm]
    if cfg.embed_scale is not None:
        x = x * jnp.asarray(cfg.embed_scale, dt)
    if cfg.embed_norm:                  # bloom word_embeddings_layernorm
        x = norm(params["ln_embed"], x)
    slopes = None
    cos = sin = None
    if cfg.position == "learned":
        x = x + params["pos_embed"]["table"][batch.positions].astype(dt)
    elif cfg.position == "alibi":
        slopes = L.alibi_slopes(cfg.num_heads)
        if "mla" not in cfg.mixer_stacks:   # the pool's heads, as the queries
            slopes = _fill_heads(
                jnp.asarray(slopes, jnp.float32).reshape(1, -1, 1),
                cfg.num_kv_heads, head_groups,
                (_kv_parts(kv)[0].shape[-2], 1)).reshape(-1)
    elif cfg.position == "rope":
        cos, sin = L.rope_freqs(cfg.rotary_dim, cfg.max_seq_len,
                                cfg.rope_theta, cfg.rope_yarn)

    def layer_weights(ws):
        """One layer's weights from the scanned inputs: ``(lp, li)``,
        or ``(li,)`` alone when the weights stream from NVMe."""
        if stream is None:
            lp, li = ws
        else:
            (li,) = ws
            lp = _stream_layer(stream, li, dt, mixed_gemm=mixed_gemm)
        if quant is not None:
            lp = merge_layer(lp, quant["blocks"], li, dt,
                             mixed=mixed_gemm)
        return lp, li

    # Dense expert weights stay OUT of the scanned inputs: the scan
    # would slice each layer's out of the stack, and what feeds a Pallas
    # call is then a copy of them all (805 MB a layer at olmoe-1b-7b's
    # sizes).  The block gets the stack and its layer's index instead,
    # as it gets the stacked cache and a row offset.
    blocks = params["blocks"] if stream is None else {}
    experts = None
    if "wi" in blocks.get("experts", {}):
        experts = blocks["experts"]
        blocks = {k: v for k, v in blocks.items() if k != "experts"}

    def branch(y):
        """A residual branch's output as it joins the stream."""
        return y if cfg.residual_scale == 1.0 \
            else y * jnp.asarray(cfg.residual_scale, dt)

    def ffn(x, o, lp, li, skip=None):
        """A layer's second half: the residual, the MLP or the experts
        → (x, stats, skip).  In a shortcut-connected layer
        (``moe_shortcut``) every sublayer has a dense MLP; the first
        holds the experts too, which read the same normed input and
        whose output is ``skip``: it joins the stream behind the last
        sublayer's MLP and nothing between reads it."""
        kw = dict(comm=comm, valid=batch.token_valid,
                  sharded=shard_mesh is not None, routing=with_routing)
        with jax.named_scope("ffn"):
            x = x + branch(o)
            h = norm(lp["ln2"], x)
            if not cfg.moe_shortcut:
                d, stats = _ffn(cfg, lp, h, dt, act, experts=None
                                if experts is None else (experts, li), **kw)
                return x + branch(d), stats, None
            d, stats = _ffn(cfg, {"mlp": lp["mlp"]}, h, dt, act, **kw)
            if "gate" not in lp:
                return x + d + skip, None, None
            skip, stats = _ffn(cfg, {"gate": lp["gate"]}, h, dt, act,
                               experts=(experts, li // P), **kw)
        return x + d, stats, skip

    def block(x, lp, pool, layer, li, kind, rec=None, rank=None,
              skip=None):
        """One layer's mathematics.  ``pool`` is the stacked paged
        cache, which holds the layer where ``layer`` says
        (``_layer_of``).  ``li``: the layer's index in ``blocks``, for
        weights kept stacked.  ``kind``: its kind, static.  ``rec``: the
        stacked state rows of a model with recurrent layers; a hybrid
        layer returns them updated, last.  ``rank``: a "kda" or "mamba"
        layer's rank among the layers that hold a state (it holds no
        blocks; an "mla" layer, and a "full" layer beside such kinds,
        holds no state, and ``layer`` says where in the pool its blocks
        lie).  ``skip``: the expert output a shortcut-connected layer
        carries to its end (``ffn``), which a layer of a model whose
        mixers are stacked by kind returns behind ``rec``."""
        if kind in ("kda", "mla", "mamba"):
            h = norm(lp["ln1"], x)
            if kind == "mamba":
                with jax.named_scope("ssm"):
                    o, rec = _ssm_mixer(cfg, lp["mamba"], h, rec, rank,
                                        batch, runs, dt, kernel=state_kernel)
            else:
                with jax.named_scope("attn"):
                    if kind == "kda":
                        o, rec = _kda_mixer(cfg, lp["kda"], h, rec, rank,
                                            batch, runs, dt,
                                            kernel=state_kernel)
                    else:
                        o, pool = _latent_attention(
                            cfg, lp["mla"], h, pool, layer, batch, runs,
                            cos, sin, dt, block_size, max_blocks_per_seq,
                            tiles=tiles.get("mla"))
            x, stats, skip = ffn(x, o, lp, li, skip)
            return x, pool, stats, rec, skip
        ap = lp["full" if cfg.mixer_stacks else "attn"]
        window = cfg.attn_window if kind == "window" else None
        # named scopes at the block's seams (metadata only): a device
        # trace's operations carry them in their JAX path, which is how
        # a reader finds what the cache write or the sampler costs
        with jax.named_scope("qkv"):
            h = norm(lp["ln1"], x)
            q, k, v = _qkv_proj(cfg, ap, h, dt, cos, sin,
                                batch.positions, kind)
            if cfg.attn_gate:
                with jax.named_scope("attn_gate"):
                    g = _head_proj(h, ap["wg"], dt, cfg.num_heads,
                                   cfg.head_dim)
        with jax.named_scope("kv_write"):
            # the one seam where a pool allocated in whole memory tiles
            # shows: behind it the write and every formulation see a
            # model with the pool's heads and lanes
            q, k, v = (_fill_heads(a, cfg.num_kv_heads, head_groups,
                                   _kv_parts(pool)[0].shape[-2:])
                       for a in (q, k, v))
            pool = _write_kv(pool, k, v, batch, block_size, layer=layer)
        # a window layer's calls under a scope of their own (inside
        # ``attn``): a trace tells its kernel from the full layers'
        with jax.named_scope("attn"), (
                jax.named_scope("attn_window") if window
                else contextlib.nullcontext()):
            if attn_impl == "pallas":
                o = _paged_attention_pallas(
                    pool, q, batch, block_size, max_blocks_per_seq,
                    scale, shard_mesh=shard_mesh, slopes=slopes,
                    layer=layer, tiles=tiles[kind], window=window)
            else:
                o = _paged_attention(pool, q, batch, block_size,
                                     max_blocks_per_seq, scale,
                                     slopes=slopes, layer=layer,
                                     window=window)
            o = _cut_heads(o, cfg.num_kv_heads, head_groups, cfg.num_heads,
                           cfg.head_dim)
        with jax.named_scope("attn_out"):
            if cfg.attn_gate:
                with jax.named_scope("attn_gate"):
                    o = o * jax.nn.sigmoid(
                        g.astype(jnp.float32)).astype(o.dtype)
            o = _out_proj(o, ap["wo"], dt)
            if cfg.attn_out_bias:
                o = o + ap["bo"].astype(dt)
            if cfg.sandwich_norm:
                o = norm(lp["ln1_post"], o)
            if cfg.attn_out_scale != 1.0:
                o = o * jnp.asarray(cfg.attn_out_scale, dt)
        if cfg.mixer_stacks:
            # a "full" layer beside kinds that hold no attention
            x, stats, skip = ffn(x, o, lp, li, skip)
            return x, pool, stats, rec, skip
        if kind == "hybrid":
            # the mixer reads the same normed input as the attention
            with jax.named_scope("ssm"):
                m, rec = _ssm_mixer(
                    cfg, lp["ssm"], h * jnp.asarray(cfg.ssm_in_scale, dt),
                    rec, li, batch, runs, dt, kernel=state_kernel)
                o = o + m * jnp.asarray(cfg.ssm_out_scale, dt)
        o = branch(o)
        with jax.named_scope("ffn"):
            if not cfg.parallel_block:
                x = x + o
                h = norm(lp["ln2"], x)
            elif cfg.parallel_separate_norms:
                h = norm(lp["ln2"], x)  # gpt-neox: MLP norms the original x
            # parallel residual (falcon/phi): MLP reads the same ln1 output
            d, stats = _ffn(cfg, lp, h, dt, act, comm=comm,
                            valid=batch.token_valid,
                            sharded=shard_mesh is not None,
                            experts=None if experts is None
                            else (experts, li), routing=with_routing)
            if cfg.sandwich_norm:
                d = norm(lp["ln2_post"], d)
            d = branch(d)
        if rec is not None:
            return x + d, pool, stats, rec
        if cfg.parallel_block:
            return x + o + d, pool, stats
        return x + d, pool, stats

    # what the Pallas kernel's grid walks is the same for every layer:
    # cut the batch into query tiles here, once, outside the scan (a
    # window layer's call finds its tiles' first blocks itself)
    tiles = {}
    if attn_impl == "pallas" and "mla" in cfg.mixer_stacks:
        # a layer holds ONE kind of cache: the latent layers' kernel
        # walks tiles of its own heights; a "kda" layer reads no block
        tiles = {"mla": _latent_tiles(cfg, kv, batch, block_size,
                                      max_blocks_per_seq)}
    elif attn_impl == "pallas" and (not cfg.mixer_stacks
                                    or "full" in cfg.mixer_stacks):
        # (beside "mamba" layers, which read no block, the "full"
        # layers' calls are the paged kernel's)
        tiles = dict.fromkeys(cfg.layer_kinds, _query_tiles(
            kv, batch, block_size, max_blocks_per_seq))
    rows = _kv_parts(kv)[0].shape[1]       # a layer's blocks + trash row

    def at(li):
        """Where layer ``li`` of ``blocks`` keeps its rows in the pool
        (behind the leading dense layers')."""
        return ((li + lead if lead else li) * rows, rows)

    def outside(x, pool, stack, first, n, layer0):
        """``n`` layers of ``stack`` from its row ``first``, one by one
        (the leading dense layers, a last period cut short).
        ``layer0``: the first one's index in the model."""
        stats = []
        for i in range(n):
            x, pool, st = block(
                x, jax.tree.map(lambda a: a[first + i], stack), pool,
                ((layer0 + i) * rows, rows), first + i,
                cfg.layer_kinds[layer0 + i])
            stats.append(st)
        return x, pool, stats

    def carried(carry, ws):
        # the cache rides the scan as a carry that each layer updates in
        # place, and the layer is an offset into the stacked pool (no
        # per-layer slice, no second pool).  The body holds one period
        # of the layer pattern, each layer of a static kind
        x, pool, *state = carry
        if P == 1:
            # a period of one layer is that layer: the general path
            # below gives the same numbers, but another compiled program
            # for every model the system served before it had a pattern
            lp, li = layer_weights(ws)
            x, pool, stats, *state = block(x, lp, pool, at(li), li,
                                           pattern[0], *state)
            return (x, pool, *state), stats
        stats = []
        for j, kind in enumerate(pattern):
            lp, li = layer_weights(jax.tree.map(lambda a: a[j], ws))
            x, pool, st = block(x, lp, pool, at(li), li, kind)
            stats.append(st)
        return (x, pool), (None if stats[0] is None else jax.tree.map(
            lambda *v: jnp.stack(v), *stats))

    def periods_of(a):
        """``a``'s rows of the whole periods: a layer a row, or with a
        longer period a period a row."""
        a = a if not tail else a[:periods * P]
        return a if P == 1 else a.reshape((periods, P) + a.shape[1:])

    def flat(kv):
        return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), kv)

    outside_stats = []
    if cfg.mixer_stacks:
        x, pool, state, stats, outside_stats = _stacked_layers(
            cfg, params, blocks, block, x, flat(kv), rec, rows)
    else:
        layer_ids = periods_of(jnp.arange(cfg.num_layers - lead,
                                          dtype=jnp.int32))
        layers = ((layer_ids,) if stream is not None
                  else (jax.tree.map(periods_of, blocks), layer_ids))
        pool = flat(kv)
        if lead:
            x, pool, _ = outside(x, pool, params["dense_blocks"], 0, lead, 0)
        (x, pool, *state), stats = jax.lax.scan(
            carried, (x, pool) if rec is None else (x, pool, rec), layers)
        if tail:
            x, pool, outside_stats = outside(x, pool, blocks, periods * P,
                                             tail, lead + periods * P)
    new_kv = jax.tree.map(lambda a, o: a.reshape(o.shape), pool, kv)
    if rec is not None:
        new_kv = {"kv": new_kv, "ssm": state[0][0], "conv": state[0][1]}

    with jax.named_scope("unembed"):
        logits = _unembed(cfg, params, embed_tab, x, batch, norm, dt, comm)
    out = (logits, new_kv)
    if with_moe_stats or with_routing:
        if P > 1 or cfg.mixer_stacks:
            # the scan's [periods, P, ...] and the tail's -> [layers, ...]
            stats = jax.tree.map(
                lambda a, *tail: jnp.concatenate(
                    [a.reshape((-1,) + a.shape[2:])]
                    + [t[None] for t in tail]),
                stats, *outside_stats)
        if with_routing:
            stats, ids = stats
        if with_moe_stats:       # per-layer [L, 3] -> the step's [3]
            out += (jnp.stack(
                [stats[:, 0].sum(), stats[:, 1].max(), stats[:, 2].sum()]
                + [stats[:, i].sum() for i in range(3, stats.shape[1])]),)
        if with_routing:
            out += (ids,)
    return out


# A period of a model whose mixers are stacked by kind is a scan's body,
# and every layer in it is traced, lowered and compiled on its own.  Up to
# this many layers a period that is how it stays (Ling's six, LongCat's
# two: the programs they compiled to); in a longer period the layers of
# one kind in a row run as a rolled loop over ONE traced layer.  The ten
# layers of granite-4.0-h's period (Mamba-2 x5, attention, Mamba-2 x4)
# took the chip's compiler 66-74 s a step program where the
# configuration's set-up has four of them to build and the machine's
# compile cache room for none; as three bodies they take 20 and run 1.6%
# faster (PERF.md section 6, PR 52).
UNROLLED_PERIOD = 8


def _stacked_layers(cfg, params, blocks, block, x, pool, rec, rows: int):
    """The layers of a model whose mixers are stacked by kind
    (``TransformerConfig.mixer_stacks``), as ``layer_plan`` says: the
    leading dense layers, one scan over the whole periods, a last period
    cut short.  A layer reads its kind's stack at its rank among the
    layers of that kind, and its cache likewise: a "kda" or "mamba"
    layer its state rows, an "mla" or "full" layer its blocks of the pool
    (``rows`` a layer).  Inside a period of more than ``UNROLLED_PERIOD``
    layers, the layers of one kind in a row run as a rolled loop of their
    own (``run_of``).
    ``block``: ``ragged_forward``'s.  → (x, pool, [rec], the scan's
    stats [periods, P, ...], the tail's)."""
    stacks = cfg.mixer_stacks
    pattern = cfg.layer_pattern
    P = len(pattern)
    lead, periods, tail = cfg.layer_plan
    per_period = {k: pattern.count(k) for k in stacks}
    # what a period's first layer alone holds (``moe_shortcut``)
    firsts = ("gate",) if cfg.moe_shortcut else ()
    per_period.update({k: 1 for k in firsts})
    in_pattern = [pattern[:j].count(kind) for j, kind in enumerate(pattern)]
    before = {k: cfg.kind_rank(lead, k) for k in stacks}
    # the period's runs of one kind: (kind, first layer, layers)
    runs = []
    for j, kind in enumerate(pattern):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, j, 1])
    rolled = P > UNROLLED_PERIOD and not cfg.moe_shortcut

    def one(x, pool, rec, lp, li, kind, rank, skip=None):
        return block(x, lp, pool, (rank * rows, rows), li, kind, rec,
                     rank=rank, skip=skip)

    def outside(x, pool, rec, stack, first, n, layer0):
        stats = []
        for i in range(n):
            layer = layer0 + i
            x, pool, st, rec, _ = one(
                x, pool, rec, stack_layer(cfg, stack, layer,
                                          layer0 - first), first + i,
                cfg.layer_kinds[layer], cfg.kind_rank(layer))
            stats.append(st)
        return x, pool, rec, stats

    def layer_of(name, sub, j, period):
        """Layer ``j`` of a period's weights of ``name``, out of the
        period's rows of the stack (``periods_of``), or for a shortcut-
        connected model out of the whole stack."""
        row = in_pattern[j] if name in stacks else 0 if name in firsts \
            else j
        if not cfg.moe_shortcut:
            return jax.tree.map(lambda a: a[row], sub)
        # read where it lies, at its own row of the stack: cut out of a
        # period's rows (the stack viewed ``[periods, rows a period,
        # ...]``) a layer is copied whole on its way into its products,
        # every byte of it a step (PERF.md section 6, PR 49)
        row += period * per_period.get(name, P)
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, row, keepdims=False), sub)

    def carried(carry, ws):
        x, pool, rec = carry
        period_w, period = ws
        stats, skip = [], None
        for j, kind in enumerate(pattern):
            lp = {name: layer_of(name, sub, j, period)
                  for name, sub in period_w.items()
                  if (name not in stacks or name == kind)
                  and (name not in firsts or j == 0)}
            x, pool, st, rec, skip = one(
                x, pool, rec, lp, period * P + j, kind,
                before[kind] + period * per_period[kind] + in_pattern[j],
                skip)
            if st is not None:
                stats.append(st)
        return (x, pool, rec), (jax.tree.map(lambda *v: jnp.stack(v), *stats)
                                if stats else None)

    def run_of(carry, period_w, period, kind, j0, n):
        """Layers ``[j0, j0 + n)`` of a period, all of ``kind``, as ONE
        traced layer in a rolled loop: each trip reads its layer's
        weights where they lie in the period's rows, at a row the trip
        counts, as the layer scan of a model of one block type does.
        → (carry, the layers' stats [n, ...])."""
        def trip(carry, t):
            x, pool, rec = carry
            lp = {name: jax.tree.map(
                lambda a, row=(in_pattern[j0] if name in stacks else j0) + t:
                jax.lax.dynamic_index_in_dim(a, row, keepdims=False), sub)
                for name, sub in period_w.items()
                if name not in stacks or name == kind}
            x, pool, st, rec, _ = one(
                x, pool, rec, lp, period * P + j0 + t, kind,
                before[kind] + period * per_period[kind] + in_pattern[j0]
                + t)
            return (x, pool, rec), st

        return jax.lax.scan(trip, carry, jnp.arange(n, dtype=jnp.int32))

    def carried_runs(carry, ws):
        """``carried`` for a period long enough to roll: a run of
        several layers a loop, a layer alone as it is."""
        period_w, period = ws
        stats = []
        for kind, j0, n in runs:
            if n > 1:
                carry, st = run_of(carry, period_w, period, kind, j0, n)
                stats.append(st)
                continue
            for j in range(j0, j0 + n):
                x, pool, rec = carry
                lp = {name: layer_of(name, sub, j, period)
                      for name, sub in period_w.items()
                      if name not in stacks or name == kind}
                x, pool, st, rec, _ = one(
                    x, pool, rec, lp, period * P + j, kind,
                    before[kind] + period * per_period[kind]
                    + in_pattern[j])
                carry = (x, pool, rec)
                stats.append(jax.tree.map(lambda a: a[None], st))
        return carry, jax.tree.map(lambda *v: jnp.concatenate(v), *stats)

    def periods_of(name, a):
        n = per_period.get(name, P)
        return a[:periods * n].reshape((periods, n) + a.shape[1:])

    if lead:
        x, pool, rec, _ = outside(x, pool, rec, params["dense_blocks"], 0,
                                  lead, 0)
    if cfg.moe_shortcut:
        # the stacks stay whole outside the scan's sliced inputs
        (x, pool, rec), stats = jax.lax.scan(
            lambda carry, period: carried(carry, (blocks, period)),
            (x, pool, rec), jnp.arange(periods, dtype=jnp.int32))
    else:
        (x, pool, rec), stats = jax.lax.scan(
            carried_runs if rolled else carried, (x, pool, rec),
            ({name: jax.tree.map(lambda a, name=name: periods_of(name, a),
                                 sub) for name, sub in blocks.items()},
             jnp.arange(periods, dtype=jnp.int32)))
    outside_stats = []
    if tail:
        x, pool, rec, outside_stats = outside(
            x, pool, rec, blocks, periods * P, tail, lead + periods * P)
    return x, pool, [rec], stats, outside_stats


def _unembed(cfg, params, embed_tab, x, batch, norm, dt, comm):
    """``ragged_forward``'s tail, under its ``unembed`` scope: final
    norm and float32 logits."""
    # logits only at each sequence's last scheduled token
    # (reference kernel: gather_for_logits / logits_gather) — or, on a
    # speculative verify batch, at every position of each sequence's
    # draft window ([S, W] gather; -1 pads read token 0 and produce
    # garbage rows the caller masks, exactly like logits_idx == -1)
    if batch.verify_idx is not None:
        idx = jnp.maximum(batch.verify_idx, 0)                 # [S, W]
    else:
        idx = jnp.maximum(batch.logits_idx, 0)
    last = x[idx]                                            # [S(,W), dm]
    last = norm(params["ln_f"], last)
    # the unembed is the step's other heavy TP collective: a
    # vocab-split GEMM whose logits all-gather rides the tile-
    # decomposed ppermute chain under a comm plan (pure data movement
    # — bitwise-identical to the serial gather)
    if cfg.tie_embeddings:
        wmat = embed_tab["table"].astype(dt).T
        if comm is not None and comm.unembed:
            logits = shard_matmul_allgather(last, wmat, comm, dt)
        else:
            logits = last @ wmat
    else:
        k = params["lm_head"]["kernel"]
        if comm is not None and comm.unembed and _dense_weight(k):
            logits = shard_matmul_allgather(last, k.astype(dt), comm, dt)
        else:
            logits = last @ k.astype(dt)
        if cfg.head_bias:
            logits = logits + params["lm_head"]["bias"].astype(dt)
    if cfg.head_scale != 1.0:
        logits = logits * jnp.asarray(cfg.head_scale, dt)
    return logits.astype(jnp.float32)


def pipelined_ragged_step(cfg: TransformerConfig, params, quant, kv,
                          batch: RaggedBatch, prev_toks, rng, sample_fn,
                          block_size: int, max_blocks_per_seq: int,
                          **fw_kwargs) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One serving pipeline stage, entirely on device: substitute
    deferred feedback tokens from the previous step's on-device samples,
    run the ragged forward, sample every slot's next token.

    ``prev_toks``: [max_seqs] i32, the previous step's sample output
    (still on device — the engine reads a step's tokens back only after
    dispatching the next one).  ``batch.feedback_src[t] == s`` means
    token ``t``'s id is ``prev_toks[s]`` rather than
    ``batch.token_ids[t]``; -1 keeps the host-staged id.  ``rng`` is the
    caller's BASE key; each row samples with a key folded by its
    (uid, position) — see ``sampler.row_keys`` — so sampled values are
    invariant to scheduling (pipeline depth, chunking, prefix-cache
    hits).  ``sample_fn(logits, keys)`` consumes the per-row keys
    (greedy ignores them and XLA drops the fold).  Returns (sampled
    tokens [max_seqs] i32, new_kv); rows of the token output whose
    ``batch.logits_idx`` is -1 are garbage (callers mask by the
    schedule, exactly like the logits of :func:`ragged_forward`).

    On a speculative verify batch (``batch.verify_idx`` [S, W] present)
    the step samples EVERY window position and returns [S, W] tokens:
    column ``j`` is the model's choice for the token AFTER window
    position ``j``, keyed by ``fold_in(fold_in(rng, uid), pos_j + 1)``
    — the identical fold the single-sample path applies, so column 0 of
    a non-drafting row is bit-for-bit the legacy sample and a drafting
    row's columns reproduce the exact non-speculative stream
    (acceptance is a host-side prefix compare at collect).
    A sparse-expert model's token output carries ``MOE_STAT_ROWS``
    further rows behind the slots' (``with_stats`` below).
    ``prev_toks`` may then be the previous verify step's [S, W] output;
    feedback reads its column 0 (markers are only ever speculated for
    non-drafting rows, whose sample lives there)."""
    fb = batch.feedback_src
    if fb is not None:
        prev = prev_toks if prev_toks.ndim == 1 else prev_toks[:, 0]
        tok = jnp.where(fb >= 0, prev[jnp.maximum(fb, 0)],
                        batch.token_ids)
        batch = batch._replace(token_ids=tok)
    moe = cfg.num_experts > 1
    logits, new_kv, *stats = ragged_forward(
        cfg, params, kv, batch, block_size, max_blocks_per_seq,
        quant=quant, with_moe_stats=moe, **fw_kwargs)

    def with_stats(toks):
        # a sparse-expert model's routing statistics ride the sampled
        # tokens' own readback as ``moe_stat_rows`` trailing rows (the
        # feedback gather never reaches them: slots are < max_seqs)
        if not moe:
            return toks
        n = moe_stat_rows(cfg)
        rows = stats[0].astype(toks.dtype).reshape(
            (n,) + (1,) * (toks.ndim - 1))
        return jnp.concatenate([toks, jnp.broadcast_to(
            rows, (n,) + toks.shape[1:])])

    if batch.verify_idx is not None:
        S, W = batch.verify_idx.shape
        vidx = jnp.maximum(batch.verify_idx, 0)
        # window column j holds the token AT sequence position
        # positions[vidx]; its sample therefore lands at position + 1 —
        # the same "context length after the token" index row_keys folds
        wpos = batch.positions[vidx] + 1                       # [S, W]
        with jax.named_scope("sample"):
            keys = window_keys(rng, batch.seq_uids, wpos)
            flat = sample_fn(logits.reshape(S * W, -1),
                             keys.reshape((S * W,) + keys.shape[2:]))
        return with_stats(flat.reshape(S, W)), new_kv
    with jax.named_scope("sample"):
        keys = row_keys(rng, batch.seq_uids, batch.context_lens)
        toks = sample_fn(logits, keys)
    return with_stats(toks), new_kv
