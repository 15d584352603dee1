"""Paged (blocked) decode attention — Pallas TPU kernel.

TPU-native analog of the reference FastGen kernel family
(``inference/v2/kernels/ragged_ops/blocked_flash`` — flash attention over
a block table, ``atom_builder`` splitting sequences into fixed KV atoms).

Where the XLA formulation in ``inference/model.py:_paged_attention``
gathers every scheduled token's *entire* padded context
(``kv_layer[tables]`` → [T, max_blocks, bs, 2, Hkv, D]) through HBM and
then re-reads it for the attention einsums, this kernel streams each
token's KV blocks through VMEM once with an online softmax, keeping the
(m, l, acc) running state on-chip:

* grid (T, num_blocks): one step attends one token (all heads) to one KV
  block — the block carries every kv head so the trailing block dims are
  full-size (a Mosaic tiling requirement) and DMA count stays at T×nb;
* the block table and positions ride scalar prefetch
  (``PrefetchScalarGridSpec``) so the kv BlockSpec's index_map picks the
  DMA'd block dynamically — paged indirection happens in the DMA engine,
  not as a gather;
* blocks past a token's position are skipped (``pl.when``) — budget
  padding tokens and table padding (-1 → trash row) contribute nothing;
* GQA: a static (unrolled) loop over kv heads, one [rep, D]×[D, bs] MXU
  dot per kv head per block.

CPU tests run the same kernel in interpret mode.  ``InferenceEngine``
probes this kernel against the XLA formulations at build time and keeps
whichever is fastest on the running backend at the engine's shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(tables_ref, pos_ref, *rest,
            block_size: int, scale: float,
            num_kv_heads: int, rep: int, alibi: bool, kv_quant: bool):
    # optional trailing inputs (order: kv scales, alibi slopes) before
    # the output and scratch refs
    rest = list(rest)
    if kv_quant:
        rest.pop(0)     # the scales' row offset: the index map's alone
    q_ref, kv_ref = rest.pop(0), rest.pop(0)
    ks_ref = rest.pop(0) if kv_quant else None
    slopes_ref = rest.pop(0) if alibi else None
    o_ref, acc_ref, m_ref, l_ref = rest
    t = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the whole block is past this token's position → nothing to add
    @pl.when(j * block_size <= pos)
    def _compute():
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rep, block_size), 1)
        keep = cols <= pos
        for h in range(num_kv_heads):          # static unroll (GQA groups)
            q = q_ref[0, h * rep:(h + 1) * rep, :]         # [rep, D]
            k = kv_ref[0, :, 0, h, :]                      # [bs, D]
            v = kv_ref[0, :, 1, h, :]                      # [bs, D]
            if kv_quant:    # in-VMEM dequant: HBM only streamed codes
                k = (k.astype(jnp.float32)
                     * ks_ref[0, :, 0, h][:, None]).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * ks_ref[0, :, 1, h][:, None]).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rep, bs]
            if alibi:       # ALiBi: slope_h * absolute key position
                s = s + (slopes_ref[h, :][:, None]
                         * cols.astype(jnp.float32))
            s = jnp.where(keep, s, NEG_INF)
            sl = slice(h * rep, (h + 1) * rep)
            m_prev, l_prev = m_ref[sl, :], l_ref[sl, :]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[sl, :] = m_new
            l_ref[sl, :] = l_prev * corr + p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [rep, D]
            acc_ref[sl, :] = acc_ref[sl, :] * corr + pv

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(kv_layer, q, seq_slot, positions, block_tables,
                    block_size: int, max_blocks_per_seq: int, scale: float,
                    slopes=None, layer=None):
    """kv_layer: [blocks+1, bs, 2, Hkv, D] (last row = trash), or a
    (data, scales) tuple for a quantized cache (scales
    [blocks+1, bs, 2, Hkv] f32; codes dequantized in VMEM so HBM only
    streams the 1-byte payloads);
    q: [T, H, D]; seq_slot/positions: [T] i32;
    block_tables: [max_seqs, max_blocks] i32 (-1 pad) → out [T, H, D].
    ``slopes``: optional ALiBi per-head slopes, any shape reshapeable to
    [Hkv, rep] in head order h = hkv*rep + r (reference analog: the alibi
    operand of the inference softmax kernels, csrc/transformer/inference/
    csrc/softmax.cu).
    ``layer``: ``(base, rows)`` when ``kv_layer`` is the stacked cache
    viewed as ``[L * rows, ...]`` and this call attends layer ``li``,
    whose ``rows`` rows (its blocks, then its trash row) start at row
    ``base = li * rows``; ``None`` is a pool of one layer.  The codes
    need only the offset: the index map picks row ``base + block`` as
    it picks any row, and the DMA engine reads it where it lies.  The
    scales are cut to the layer's rows first and indexed without the
    offset, because Mosaic wants them in a lane-padded layout of its
    own: a layer's worth is relaid per call as before, never the
    stack's."""
    kv_scales = None
    if isinstance(kv_layer, tuple):
        kv_layer, kv_scales = kv_layer
    T, H, D = q.shape
    nblocks, bs, _, Hkv, _ = kv_layer.shape
    rep = H // Hkv
    nb = max_blocks_per_seq

    base, rows = (0, nblocks) if layer is None else layer
    tables = block_tables[seq_slot, :nb]                   # [T, nb]
    tables = (jnp.where(tables < 0, rows - 1, tables)
              + base).astype(jnp.int32)
    positions = positions.astype(jnp.int32)

    def _kv_index(t, j, tbl, pos, *_):
        # clamp past-position block indices to the last needed block:
        # consecutive grid steps then revisit the same block and Pallas
        # skips the DMA entirely (the kernel skips the compute)
        jj = jnp.minimum(j, pos[t] // bs)
        return (tbl[t, jj], 0, 0, 0, 0)

    def _ks_index(t, j, tbl, pos, base):
        jj = jnp.minimum(j, pos[t] // bs)
        return (tbl[t, jj] - base[0], 0, 0, 0)

    alibi = slopes is not None
    kv_quant = kv_scales is not None
    prefetch = [tables, positions]
    in_specs = [
        pl.BlockSpec((1, H, D), lambda t, j, *_: (t, 0, 0)),
        pl.BlockSpec((1, bs, 2, Hkv, D), _kv_index),
    ]
    operands = [q, kv_layer]
    if kv_quant:
        prefetch.append(jnp.reshape(base, (1,)).astype(jnp.int32))
        in_specs.append(pl.BlockSpec((1, bs, 2, Hkv), _ks_index))
        operands.append(jax.lax.dynamic_slice_in_dim(kv_scales, base, rows))
    if alibi:
        in_specs.append(pl.BlockSpec((Hkv, rep), lambda t, j, *_: (0, 0)))
        operands.append(jnp.asarray(slopes, jnp.float32)
                        .reshape(Hkv, rep))

    grid = (T, nb)
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, scale=scale,
                          num_kv_heads=Hkv, rep=rep, alibi=alibi,
                          kv_quant=kv_quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, D), lambda t, j, *_: (t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H, D), q.dtype),
        interpret=_use_interpret(),
        name="paged_attention",
    )(*prefetch, *operands)
    return out
