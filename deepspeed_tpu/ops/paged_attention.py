"""Paged (blocked) attention over query tiles — Pallas TPU kernel.

TPU-native analog of the reference FastGen kernel family
(``inference/v2/kernels/ragged_ops/blocked_flash`` — flash attention over
a block table, ``atom_builder`` splitting sequences into fixed KV atoms).

A serving step's tokens fall into *runs*: consecutive rows of the ragged
batch that belong to one sequence at consecutive positions (a decode
token is a run of one, a speculative verify window a run of ``k + 1``, a
prefill chunk a run of up to the token budget).  ``query_tiles`` cuts
every run into *tiles* of at most ``height`` rows, once a step and
outside the layer scan, and gathers each tile's block-table row; budget
padding belongs to no tile.  The kernel then streams KV blocks through
VMEM once per tile, not once per token:

* grid ``(tiles, blocks)``, both traced: a step runs as many grid rows
  as it has tiles, each as long as the deepest tile's context (the
  compiled bucket ``max_blocks_per_seq`` only bounds it).  One grid
  step attends one tile (all heads) to one KV block; the block carries
  every kv head so the trailing block dims are full-size (a Mosaic
  tiling requirement);
* the tiles' tables, first rows, first positions and lengths ride scalar
  prefetch (``PrefetchScalarGridSpec``): the kv BlockSpec's index map
  picks the DMA'd block, the query's picks the tile's first row as an
  element offset into ``[T, H, D]`` — paged indirection and ragged rows
  both happen in the DMA engine, never as a gather;
* per kv head the products are ``[height * rep, D] x [D, bs]`` and
  ``[height * rep, bs] x [bs, D]``: the tile's queries are folded to
  that shape once, at its first block, and kept in VMEM; the causal mask
  is ``col <= first_pos + row``; the online softmax keeps (m, l, acc)
  per row in f32 across the tile's blocks;
* blocks past the tile's last position are skipped (``pl.when``) and
  their index maps stay on the last needed block, so nothing is DMA'd
  for them;
* the output is written by the kernel's own DMAs, ``length`` rows of it
  and no more (groups of 8 rows, then single rows: static sizes, a
  traced count): the row after a tile's last belongs to another run.

``paged_attention`` runs the one kernel body at two heights: runs of at
most ``SHORT`` rows (decode tokens, verify windows) at ``SHORT``, longer
ones (prefill chunks) in tiles of ``LONG``.  Which one a run takes is a
property of the batch, not an option.

A *window* layer (``window=W``: a query sees its last ``W`` keys, its
own among them) SKIPS what lies behind the window and does not only mask
it: a tile's grid row starts at the block that holds position ``first
query - (W - 1)`` and ends at the block of its last query, at most
``ceil((W + height) / block_size) + 1`` blocks whatever the context, and
the mask cuts inside the first of them.  Those calls are named
``paged_attention_w_h<height>``, so that a trace tells them from the
full layers'.

CPU tests run the same kernel in interpret mode.  ``InferenceEngine``
probes this kernel against the XLA formulations at build time and keeps
whichever is fastest on the running backend at the engine's shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tile heights: [SHORT * rep, D] is what the MXU pads a decode token's
# [rep, D] to anyway; LONG * rep rows fill it at rep = 4
SHORT, LONG = 8, 128


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


class TileList(NamedTuple):
    """The tiles of one height, padded to a static bound."""
    tables: jnp.ndarray     # [n, nb] i32 rows of a layer's pool (pads → trash)
    row: jnp.ndarray        # [n] i32 first row in the batch
    pos: jnp.ndarray        # [n] i32 position of that row in its sequence
    length: jnp.ndarray     # [n] i32 rows, 1..height
    count: jnp.ndarray      # [] i32 real tiles; the rest is padding
    blocks: jnp.ndarray     # [] i32 KV blocks the deepest real tile needs
    wblocks: jnp.ndarray    # [] i32 the same in a window layer (the most
                            # blocks one tile's window touches)


class QueryTiles(NamedTuple):
    short: TileList
    long: TileList


def tile_counts(run_lengths: Sequence[int]) -> Tuple[int, int, int]:
    """(short tiles, long tiles, real rows in the long tiles) of a step
    whose runs have these lengths — the host's count of what
    ``query_tiles`` builds on the device."""
    n_short = sum(1 for n in run_lengths if 0 < n <= SHORT)
    long_runs = [n for n in run_lengths if n > SHORT]
    return n_short, sum(-(-n // LONG) for n in long_runs), sum(long_runs)


def window_blocks(pos, length, window: int, block_size: int):
    """(first, last) KV block that the window layer's tile starting at
    position ``pos`` with ``length`` rows has to read."""
    first = jnp.maximum(pos - (window - 1), 0) // block_size
    return first, (pos + jnp.maximum(length, 1) - 1) // block_size


def query_tiles(seq_slot, positions, token_valid, block_tables,
                block_size: int, max_blocks_per_seq: int,
                trash: int, window: int = None) -> QueryTiles:
    """Cut a ragged batch into query tiles, on the device, once a step.

    seq_slot/positions: [T] i32, token_valid: [T] bool,
    block_tables: [max_seqs, max_blocks] i32 (-1 pad → row ``trash`` of
    a layer's pool).  A run is a maximal stretch of valid rows of one
    slot at consecutive positions; a slot holds at most one run a step
    (``StateManager.build_batch`` schedules a sequence once), which
    bounds the lists: ``max_seqs`` short tiles, ``T // LONG`` full long
    tiles and one partial one a long run.  ``window``: the model's
    attention window where it has window layers (``wblocks``)."""
    T = seq_slot.shape[0]
    max_seqs = block_tables.shape[0]
    i = jnp.arange(T, dtype=jnp.int32)
    slot = seq_slot.astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    valid = token_valid
    follows = (valid[1:] & valid[:-1] & (slot[1:] == slot[:-1])
               & (pos[1:] == pos[:-1] + 1))
    first = valid & jnp.concatenate([jnp.ones(1, bool), ~follows])
    last = valid & jnp.concatenate([~follows, jnp.ones(1, bool)])
    start = jax.lax.cummax(jnp.where(first, i, -1))          # run's first row
    end = jax.lax.cummin(jnp.where(last, i, T), reverse=True)
    run_len = end - start + 1
    off = i - start
    is_short = run_len <= SHORT

    def collect(flag, length, bound):
        rows = jnp.flatnonzero(flag, size=bound, fill_value=0).astype(
            jnp.int32)
        count = jnp.minimum(flag.sum(), bound).astype(jnp.int32)
        real = jnp.arange(bound) < count
        tables = block_tables[slot[rows], :max_blocks_per_seq]
        tables = jnp.where(tables < 0, trash, tables).astype(jnp.int32)
        length = jnp.where(real, length[rows], 0)
        blocks = jnp.minimum(jnp.max(jnp.where(
            real, (pos[rows] + length - 1) // block_size + 1, 1)),
            max_blocks_per_seq)
        wblocks = blocks
        if window is not None:
            first, last = window_blocks(pos[rows], length, window,
                                        block_size)
            wblocks = jnp.minimum(
                jnp.max(jnp.where(real, last - first + 1, 1)), blocks)
        return TileList(tables, rows, pos[rows], length, count, blocks,
                        wblocks)

    short = collect(first & is_short, run_len, min(T, max_seqs))
    long = collect(valid & ~is_short & (off % LONG == 0),
                   jnp.minimum(run_len - off, LONG),
                   max(1, T // LONG + min(max_seqs, T // (SHORT + 1))))
    return QueryTiles(short, long)


def _each_row_copy(do, src, dst, sem, row, n):
    """``do`` (start or wait) every DMA that moves ``n`` rows (traced)
    of ``src`` (VMEM, from its row 0) to ``dst`` (HBM, from ``row``):
    whole groups of 8 rows, then the rest row by row.  Every size is
    static and nothing past row ``n`` is touched; all of them count on
    the one semaphore, so waiting takes the same walk."""
    g = min(8, src.shape[0], dst.shape[0])

    def copy(at, size):
        do(pltpu.make_async_copy(src.at[pl.ds(at, size)],
                                 dst.at[pl.ds(row + at, size)], sem))

    jax.lax.fori_loop(0, n // g, lambda i, _: copy(i * g, g), None)
    jax.lax.fori_loop(0, n % g, lambda i, _: copy(n // g * g + i, 1), None)


def _kernel(tables_ref, row_ref, pos_ref, len_ref, base_ref, *rest,
            height: int, block_size: int, scale: float,
            num_kv_heads: int, rep: int, alibi: bool, kv_quant: bool,
            window):
    # optional inputs (order: kv scales, alibi slopes) sit between the
    # kv block and the aliased output
    rest = list(rest)
    q_ref, kv_ref = rest.pop(0), rest.pop(0)
    ks_ref = rest.pop(0) if kv_quant else None
    slopes_ref = rest.pop(0) if alibi else None
    _, o_ref, qs_ref, ob_ref, acc_ref, m_ref, l_ref, sem = rest
    t = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(0)
    nb = pl.num_programs(1)
    R = height * rep
    pos0 = pos_ref[t]
    n = len_ref[t]
    # the KV block this grid step attends: a window layer's row starts
    # at the first block its first query's window touches
    blk = j if window is None else \
        j + window_blocks(pos0, n, window, block_size)[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # fold the tile's queries [height, H, D] to one [height * rep, D]
        # operand per kv head, once for all its blocks (row = token * rep
        # + head of the group)
        for h in range(num_kv_heads):
            qs_ref[h] = (q_ref[:, h, :] if rep == 1 else
                         q_ref[:, h * rep:(h + 1) * rep, :].reshape(
                             R, q_ref.shape[-1]))

    # the whole block is past the tile's last position → nothing to add
    @pl.when(blk * block_size <= pos0 + n - 1)
    def _compute():
        cols = blk * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (R, block_size), 1)
        # a folded row's position: row // rep tokens after the first
        qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep
        keep = cols <= qpos
        if window is not None:
            # a row whose window starts after this block is masked whole
            # here: what it then adds is wiped when its first real block
            # raises its maximum (the correction is exp(-1e30 - m) = 0)
            keep &= cols > qpos - window
        for h in range(num_kv_heads):          # static unroll (GQA groups)
            q = qs_ref[h]                                  # [R, D]
            k = kv_ref[0, :, 0, h, :]                      # [bs, D]
            v = kv_ref[0, :, 1, h, :]                      # [bs, D]
            if kv_quant:    # in-VMEM dequant: HBM only streamed codes
                k = (k.astype(jnp.float32)
                     * ks_ref[0, :, 0, h][:, None]).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * ks_ref[0, :, 1, h][:, None]).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [R, bs]
            if alibi:       # ALiBi: slope_h * absolute key position
                s = s + slopes_ref[h] * cols.astype(jnp.float32)
            s = jnp.where(keep, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_prev * corr + p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [R, D]
            acc_ref[h] = acc_ref[h] * corr + pv

    @pl.when(j == nb - 1)
    def _finalize():
        # the output rows leave by the kernel's own DMAs, double
        # buffered: this tile's start here and are waited for when the
        # next tile (or the grid) ends, so they overlap its blocks
        slot = t % 2
        start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

        @pl.when(t > 0)
        def _():
            _each_row_copy(wait, ob_ref.at[1 - slot], o_ref,
                           sem.at[1 - slot], row_ref[t - 1], len_ref[t - 1])

        for h in range(num_kv_heads):
            # unfolded in f32: Mosaic has no such shape cast for packed
            # rows narrower than a lane tile (gpt2's D = 64 in bf16)
            o = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).reshape(
                height, rep, acc_ref.shape[-1])
            ob_ref[slot, :, h * rep:(h + 1) * rep, :o.shape[-1]] = o.astype(
                ob_ref.dtype)
        mine = (ob_ref.at[slot], o_ref, sem.at[slot], row_ref[t], n)
        _each_row_copy(start, *mine)

        @pl.when(t == nt - 1)
        def _():
            _each_row_copy(wait, *mine)


def _attend(tiles: TileList, kv_layer, kv_scales, q, out, base, height: int,
            scale: float, slopes, window=None):
    """One ``pallas_call`` over ``tiles`` → ``out`` with their rows
    written (``out`` is donated to the call and returned)."""
    T, H, D = q.shape
    _, bs, _, Hkv, _ = kv_layer.shape
    rep = H // Hkv
    R = height * rep
    nb = tiles.tables.shape[1]

    def _last_block(t, j, pos, length):
        # clamp past-position block indices to the last needed block:
        # consecutive grid steps then revisit the same block and Pallas
        # skips the DMA entirely (the kernel skips the compute).  (An
        # empty list's entry 0 has length 0; whatever evaluates this for
        # it must still get a block of the table.)
        if window is None:
            return jnp.minimum(
                j, (pos[t] + jnp.maximum(length[t], 1) - 1) // bs)
        first, last = window_blocks(pos[t], length[t], window, bs)
        return jnp.minimum(first + j, last)

    def _kv_index(t, j, tbl, row, pos, length, base):
        return (tbl[t, _last_block(t, j, pos, length)] + base[0], 0, 0, 0, 0)

    def _ks_index(t, j, tbl, row, pos, length, base):
        return (tbl[t, _last_block(t, j, pos, length)], 0, 0, 0)

    def _q_index(t, j, tbl, row, *_):
        return (row[t], 0, 0)

    alibi = slopes is not None
    kv_quant = kv_scales is not None
    prefetch = [tiles.tables, tiles.row, tiles.pos, tiles.length,
                jnp.reshape(base, (1,)).astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((pl.Element(height), pl.Element(H), pl.Element(D)),
                     _q_index),
        pl.BlockSpec((1, bs, 2, Hkv, D), _kv_index),
    ]
    operands = [q, kv_layer]
    if kv_quant:
        in_specs.append(pl.BlockSpec((1, bs, 2, Hkv), _ks_index))
        operands.append(kv_scales)
    if alibi:
        # per folded row (token * rep + head of the group)
        in_specs.append(pl.BlockSpec((Hkv, R, 1), lambda t, j, *_: (0, 0, 0)))
        operands.append(jnp.tile(
            jnp.asarray(slopes, jnp.float32).reshape(Hkv, 1, rep),
            (1, height, 1)).reshape(Hkv, R, 1))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(out)

    return pl.pallas_call(
        functools.partial(_kernel, height=height, block_size=bs,
                          scale=scale, num_kv_heads=Hkv, rep=rep,
                          alibi=alibi, kv_quant=kv_quant, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles.count,
                  tiles.blocks if window is None else tiles.wblocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((Hkv, R, D), q.dtype),            # folded queries
                pltpu.VMEM((2, height) + out.shape[1:], q.dtype),  # output rows
                pltpu.VMEM((Hkv, R, D), jnp.float32),
                pltpu.VMEM((Hkv, R, 1), jnp.float32),
                pltpu.VMEM((Hkv, R, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={len(prefetch) + len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_use_interpret(),
        name=f"paged_attention{'' if window is None else '_w'}_h{height}",
    )(*prefetch, *operands)


def paged_attention(kv_layer, q, tiles: QueryTiles, scale: float,
                    slopes=None, layer=None, window=None):
    """kv_layer: [blocks+1, bs, 2, Hkv, D] (last row = trash), or a
    (data, scales) tuple for a quantized cache (scales
    [blocks+1, bs, 2, Hkv] f32; codes dequantized in VMEM so HBM only
    streams the 1-byte payloads);
    q: [T, H, D]; ``tiles``: ``query_tiles`` of the step → out [T, H, D],
    zero in the rows of no tile (budget padding).
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
    stack's.
    ``window``: a window layer's window (module docstring); ``tiles``
    must have been cut with it."""
    kv_scales = None
    if isinstance(kv_layer, tuple):
        kv_layer, kv_scales = kv_layer
    base, rows = (0, kv_layer.shape[0]) if layer is None else layer
    if kv_scales is not None:
        kv_scales = jax.lax.dynamic_slice_in_dim(kv_scales, base, rows)
    # the element-offset query window of a tile that starts in the last
    # rows reads past them: give it rows to read
    qp = jnp.pad(q, ((0, LONG), (0, 0), (0, 0)))
    # the kernel's own DMAs move whole (sublane, lane) tiles: heads and
    # head size that do not fill theirs (gpt2's 12 x 64) get an output
    # that does, cut back here
    T, H, D = q.shape
    Hp = H if H < 8 else -(-H // 8) * 8
    out = jnp.zeros((T, Hp, -(-D // 128) * 128), q.dtype)
    for tl, height in ((tiles.long, LONG), (tiles.short, SHORT)):
        out = _attend(tl, kv_layer, kv_scales, qp, out, base, height,
                      scale, slopes, window)
    return out[:, :H, :D]
