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

* grid ``(tiles,)``, traced: a launch row is one tile (all heads), and
  inside it a loop walks the tile's OWN context in *groups* of ``k``
  consecutive KV blocks and no further: a decode token at 0.5k cached
  tokens costs its eight blocks whatever the step's deepest context
  (the compiled bucket ``max_blocks_per_seq`` only bounds the table).
  One trip attends the tile to one group, ``k * block_size`` keys: per
  kv head ONE score product, mask, online-softmax update and value
  product, so the serial chain between them (a few hundred cycles
  whatever the width) is paid once a group and not once a block.  ``k``
  is a static function of the call's shapes (``kv_group``: 16 for the
  decode tokens' call at 4 and 8 kv heads of 128, 8 at 16; 16 for a
  512-row prefill tile and 8 for one of 1,024 rows, a score tile of
  2 MiB) and nothing selects it;
* the blocks are fetched by the kernel's own DMAs, one a block, from
  the pool where it lies (handed over once, ``memory_space=pl.ANY``;
  the table's entry plus the layer's base; never gathered) into one of
  two VMEM buffers ``[k, bs, 2, Hkv, D]``, a block behind another.
  While a group is attended the next one's blocks are on their way, and
  behind a tile's last group the NEXT tile's first: the DMA queue does
  not drain between tiles.  A block past the tile's last position is
  neither read nor waited for; it stays masked (the buffers are zeroed
  when the grid opens: a masked key's value still meets the second
  product).  The walk over a group's needed blocks
  (``each_group_block``) is the latent kernel's too (``ops/mla.py``).
  The tiles' tables, first rows, first positions and lengths ride
  scalar prefetch (``PrefetchScalarGridSpec``); the query's index map
  picks the tile's first row as an element offset into ``[T, H, D]`` —
  paged indirection and ragged rows both happen in the DMA engine.
  (Until PR 51 the grid was ``(tiles, groups)`` with the pool handed
  over ``k`` times, one BlockSpec a block of the group, and a table
  laid out once a step told every operand which row to show at every
  grid step: that pipeline's bookkeeping, and a grid row as long as the
  step's deepest tile for every tile, are what this form takes away,
  PERF.md section 6, PR 51.)  A DMA moves whole memory tiles, so a
  pool the kernel reads is ALLOCATED with a slab that fills them
  (``slab``: gpt2's 12 x 64 as 16 x 128, each chip's own heads under a
  tensor mesh; ``KVCacheConfig.tiled``, the engine's where it runs the
  kernel) and a quantized cache's scales lie ``[Hkv, 2 * bs]`` a block,
  a head a row (one whole tile at 8 kv heads and blocks of 64): nothing
  is cut, padded or relaid for a call, and the TPU's compiler has no
  reason to keep the block axis innermost, as it does for a pool whose
  rows are not whole tiles (it then relays the whole stack around every
  call);
* per kv head the products are ``[height * rep, D] x [D, k * bs]`` and
  ``[height * rep, k * bs] x [k * bs, D]``: the tile's queries are
  folded to that shape once a tile and kept in VMEM; the causal mask is
  ``col <= first_pos + row``; the online softmax keeps (m, l, acc) per
  row in f32 across the tile's groups;
* a head's keys are read through the buffer's 32-bit sublanes: in VMEM
  a group is ``k * bs * 2 * Hkv`` rows of ``D`` lanes, a sublane holding
  two heads in bf16 (four in int8), so head ``h``'s ``k * 64`` keys are
  one strided load, eight keys an instruction, and a shift where
  indexing ``[:, c, h, :]`` is 64 loads, 64 rotates and 56 selects a
  block (that gather, not the chain, was all of a block's 1.2 us at 8
  kv heads and 2.7 us at 16).  A type with no such view (fp8) is
  gathered as before.  A quantized cache's codes go into the products
  as they are (exact in bf16) and a head's scales, a row ``[1, k * bs]``
  in the scores' column order, multiply the scores and the weights:
  ``q . (code * scale) = (q . code) * scale``;
* the output is written by the kernel's own DMAs, ``length`` rows of it
  and no more (groups of 8 rows, then single rows: static sizes, a
  traced count): the row after a tile's last belongs to another run.

``paged_attention`` runs the one kernel body at two heights: runs of at
most ``SHORT`` rows (decode tokens, verify windows) at ``SHORT``, longer
ones (prefill chunks) in tiles of ``LONG``.  Which one a run takes is a
property of the batch, not an option.

A *window* layer (``window=W``: a query sees its last ``W`` keys, its
own among them) SKIPS what lies behind the window and does not only mask
it: a tile's loop starts at the block that holds position ``first
query - (W - 1)`` and ends at the block of its last query, at most
``ceil((W + height) / block_size) + 1`` blocks whatever the context
(its groups count from that first block, which need not be a multiple
of ``k``; the same tiles serve both kinds of layer), and the mask cuts
inside the first of them.  Those calls are named
``paged_attention_w_h<height>``, so that a trace tells them from the
full layers'.

CPU tests run the same kernel in interpret mode.  ``InferenceEngine``
takes this kernel on a TPU backend and the XLA formulations elsewhere
(``attn_impl``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tile heights: [SHORT * rep, D] is what the MXU pads a decode token's
# [rep, D] to anyway; LONG * rep rows fill it at rep = 4
SHORT, LONG = 8, 128


# A trip of a tile's loop attends a GROUP of consecutive KV blocks.  How many
# is a static function of what a call can see (``kv_group``): the largest
# power of two, at most ``GROUP_MAX`` and the table's width, whose two
# buffers (as Mosaic tiles them in VMEM) and f32 score tile fit
# ``GROUP_VMEM_BYTES`` (a quarter of the 64 MiB the call scopes), the
# score tile alone ``GROUP_SCORE_BYTES``.  Measured on the chip with the
# blocks fetched by the kernel (PERF.md section 6, PR 51): 16 blocks beat
# 8 for the decode tokens' tiles at 4 and 8 kv heads (a block's DMA is
# what is left at 4), and a prefill tile's call falls by two thirds from
# one block a trip to a score tile of 2 MiB (its accumulator is rescaled
# once a group)
GROUP_MAX = 16
GROUP_VMEM_BYTES = 16 * 1024 * 1024
GROUP_SCORE_BYTES = 2 * 1024 * 1024


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


class TileList(NamedTuple):
    """The tiles of one height, padded to a static bound."""
    tables: jnp.ndarray     # [n, nb] i32 rows of a layer's pool (pads → trash)
    row: jnp.ndarray        # [n] i32 first row in the batch
    pos: jnp.ndarray        # [n] i32 position of that row in its sequence
    length: jnp.ndarray     # [n] i32 rows, 1..height
    count: jnp.ndarray      # [] i32 real tiles; the rest is padding


class QueryTiles(NamedTuple):
    short: TileList
    long: TileList
    # the runs of at least ``wide[0]`` rows, where a caller asked for
    # them apart (the latent kernel's expanded form, ``ops/mla.py``)
    wide: TileList = None


def tile_counts(run_lengths: Sequence[int], short: int = SHORT,
                long: int = LONG, wide: Tuple[int, int] = None) -> Tuple:
    """(short tiles, long tiles, real rows in the long tiles) of a step
    whose runs have these lengths — the host's count of what
    ``query_tiles`` builds on the device at the same two heights; with
    ``wide`` (``query_tiles``') a fourth count, the wide tiles, whose
    runs the long ones then leave out.  A wide run is cut where the
    BATCH's rows pass a multiple of the height, so this count alone
    takes ``run_lengths`` to be in the batch's order, one run behind
    another from row 0: what ``RaggedState.build_batch`` does with the
    schedule it is given (a running ``cursor`` over ``requests``), and
    what ``tests/test_latent_expanded.py`` holds against the lists cut
    on the device."""
    n_short = sum(1 for n in run_lengths if 0 < n <= short)
    long_runs = [n for n in run_lengths
                 if n > short and not (wide and n >= wide[0])]
    counts = (n_short, sum(-(-n // long) for n in long_runs), sum(long_runs))
    if wide:
        at = n_wide = 0
        for n in run_lengths:
            if n >= wide[0]:
                n_wide += (at + n - 1) // wide[1] - at // wide[1] + 1
            at += n
        counts += (n_wide,)
    return counts


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def slab_rows(num_kv_heads: int, kv_dtype) -> int:
    """Rows of the memory tiles Mosaic lays a block's ``(Hkv, D)`` slab
    out in, 128 lanes each: a power of two, at least a 32-bit sublane's
    worth and at most 8 (4 kv heads in bf16 fill theirs, 12 take 16)."""
    rows = 4 // jnp.dtype(kv_dtype).itemsize
    while rows < min(num_kv_heads, 8):
        rows *= 2
    return rows


def slab(num_kv_heads: int, head_dim: int, kv_dtype) -> Tuple[int, int]:
    """(heads, lanes) of a block's slab filled up to whole memory tiles:
    what a pool the kernel reads is allocated with
    (``KVCacheConfig.tiled``) and what a block takes in VMEM."""
    return (_pad(num_kv_heads, slab_rows(num_kv_heads, kv_dtype)),
            _pad(head_dim, 128))


def block_vmem_bytes(num_kv_heads: int, head_dim: int, block_size: int,
                     kv_dtype, quant: bool = False) -> int:
    """VMEM one ``[bs, 2, Hkv, D]`` KV block takes as Mosaic tiles it
    (``slab``: 4 kv heads in bf16 take no padding, 12 take 16, 64 lanes
    take 128); a quantized cache's f32 scales ``[Hkv, 2 * bs]`` with
    it."""
    heads, lanes = slab(num_kv_heads, head_dim, kv_dtype)
    block = block_size * 2 * heads * lanes * jnp.dtype(kv_dtype).itemsize
    if quant:
        block += _pad(num_kv_heads, slab_rows(num_kv_heads, jnp.float32)
                      ) * _pad(2 * block_size, 128) * 4
    return block


def group_vmem_bytes(group: int, rows: int, num_kv_heads: int, head_dim: int,
                     block_size: int, kv_dtype, quant: bool = False) -> int:
    """VMEM a group of ``group`` KV blocks takes: every block twice
    (the kernel's two buffers, ``block_vmem_bytes``) and the f32 score
    tile ``[rows, group * bs]``."""
    return (2 * group * block_vmem_bytes(num_kv_heads, head_dim, block_size,
                                         kv_dtype, quant)
            + rows * group * block_size * 4)


def kv_group(height: int, rep: int, num_kv_heads: int, head_dim: int,
             block_size: int, kv_dtype, table_blocks: int,
             quant: bool = False) -> int:
    """KV blocks a trip of the loop of the call at ``height`` attends:
    what the kernel takes and what the host counts with
    (``group_steps``).  Nothing but the call's own shapes decides it."""
    rows = height * rep
    k = 1
    while (2 * k <= min(GROUP_MAX, table_blocks)
           and rows * 2 * k * block_size * 4 <= GROUP_SCORE_BYTES
           and group_vmem_bytes(2 * k, rows, num_kv_heads, head_dim,
                                block_size, kv_dtype, quant)
           <= GROUP_VMEM_BYTES):
        k *= 2
    return k


def group_steps(runs: Sequence[Tuple[int, int]], block_size: int, group: int,
                window: int = None) -> Tuple[int, int]:
    """(groups, needed blocks) of one layer's short call over ``runs``,
    ``(first position, rows)`` each, rows <= ``SHORT`` — the host's
    count of what the kernel does for them (a window layer with
    ``window``): a group is a trip of a tile's loop, a needed block a
    copy it starts; there is no other trip and no other copy."""
    steps = blocks = 0
    for pos, n in runs:
        first = 0 if window is None else max(pos - (window - 1),
                                             0) // block_size
        need = (pos + max(n, 1) - 1) // block_size - first + 1
        blocks += need
        steps += -(-need // group)
    return steps, blocks


def window_blocks(pos, length, window: int, block_size: int):
    """(first, last) KV block that the window layer's tile starting at
    position ``pos`` with ``length`` rows has to read — the host's
    arithmetic (numbers or arrays) for what the kernel's ``_tile_span``
    computes a tile."""
    first = np.maximum(pos - (window - 1), 0) // block_size
    return first, (pos + np.maximum(length, 1) - 1) // block_size


def query_tiles(seq_slot, positions, token_valid, block_tables,
                block_size: int, max_blocks_per_seq: int,
                trash: int, short: int = SHORT,
                long: int = LONG, wide: Tuple[int, int] = None) -> QueryTiles:
    """Cut a ragged batch into query tiles, on the device, once a step.

    seq_slot/positions: [T] i32, token_valid: [T] bool,
    block_tables: [max_seqs, max_blocks] i32 (-1 pad → row ``trash`` of
    a layer's pool).  A run is a maximal stretch of valid rows of one
    slot at consecutive positions; a slot holds at most one run a step
    (``StateManager.build_batch`` schedules a sequence once), which
    bounds the lists: ``max_seqs`` short tiles, ``T // long`` full long
    tiles and one partial one a long run.
    ``short``, ``long``: the two heights (this kernel's; the latent
    kernel of ``ops/mla.py`` cuts the same runs at its own).
    ``wide``: ``(from, height)``, for a caller with a third call: the
    runs of at least ``from`` rows leave the long list for a third one,
    cut where the BATCH's rows pass a multiple of ``height`` (a tile
    lies inside one window of ``height`` rows of the batch, at any
    offset: the caller reads whole windows); at most a tile a such run
    and one more a boundary, none where no run can be that long."""
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
    is_short = run_len <= short
    is_long = ~is_short
    n_wide = T // wide[0] if wide else 0
    if n_wide:
        n_wide += -(-T // wide[1]) - 1
        is_wide = run_len >= wide[0]
        is_long &= ~is_wide

    def collect(flag, length, bound):
        rows = jnp.flatnonzero(flag, size=bound, fill_value=0).astype(
            jnp.int32)
        count = jnp.minimum(flag.sum(), bound).astype(jnp.int32)
        real = jnp.arange(bound) < count
        tables = block_tables[slot[rows], :max_blocks_per_seq]
        tables = jnp.where(tables < 0, trash, tables).astype(jnp.int32)
        length = jnp.where(real, length[rows], 0)
        return TileList(tables, rows, pos[rows], length, count)

    return QueryTiles(
        collect(first & is_short, run_len, min(T, max_seqs)),
        collect(valid & is_long & (off % long == 0),
                jnp.minimum(run_len - off, long),
                max(1, T // long + min(max_seqs, T // (short + 1)))),
        collect(valid & is_wide & (first | (i % wide[1] == 0)),
                jnp.minimum(run_len - off, wide[1] - i % wide[1]), n_wide)
        if n_wide else None)


def _start(cp):
    cp.start()


def _wait(cp):
    cp.wait()


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

    whole = jax.lax.div(n, jnp.int32(g))
    jax.lax.fori_loop(0, whole, lambda i, _: copy(i * g, g), None)
    jax.lax.fori_loop(0, jax.lax.rem(n, jnp.int32(g)),
                      lambda i, _: copy(whole * g + i, 1), None)


def send_tile_rows(t, nt, fill, ob_ref, o_ref, sem, row_ref, len_ref):
    """A finished tile's rows out of the kernel by its own DMAs, double
    buffered: those of tile ``t`` of ``nt`` (the grid's row and rows,
    read outside any ``pl.when``) are made by ``fill(buffer)`` and start
    here; they are waited for when the next tile (or the grid) ends, so
    they overlap its blocks.  ``ob_ref``: ``[2, height, ...]`` in VMEM,
    ``o_ref`` the output in HBM, ``sem`` two DMA semaphores."""
    slot = jax.lax.rem(t, 2)

    @pl.when(t > 0)
    def _():
        _each_row_copy(_wait, ob_ref.at[1 - slot], o_ref, sem.at[1 - slot],
                       row_ref[t - 1], len_ref[t - 1])

    fill(ob_ref.at[slot])
    mine = (ob_ref.at[slot], o_ref, sem.at[slot], row_ref[t], len_ref[t])
    _each_row_copy(_start, *mine)

    @pl.when(t == nt - 1)
    def _():
        _each_row_copy(_wait, *mine)


def _tile_span(t, pos_ref, len_ref, block_size: int, window):
    """(first, last) KV block tile ``t`` has to read, in the kernel
    (``window_blocks`` is the host's).  The primitives, not ``jnp``'s
    ``//``, ``%`` and ``maximum``: each of those traces and lowers a
    nested function with the signs' handling at every use, and every
    function that holds the step lowers the kernels' bodies anew (a
    tenth of a second a kernel: seconds of a cell's set-up)."""
    bs = jnp.int32(block_size)
    last = jax.lax.div(
        pos_ref[t] + jax.lax.max(len_ref[t], jnp.int32(1)) - 1, bs)
    if window is None:
        return 0, last
    return jax.lax.div(jax.lax.max(pos_ref[t] - (window - 1), jnp.int32(0)),
                       bs), last


def each_group_block(do, tab_ref, tile, at, last, group: int, copies):
    """``do`` (start or wait) the DMAs of the blocks ``at``, ``at + 1``,
    ... of ``tile``'s table that the tile needs: at most ``group`` of
    them and none behind its block ``last``, which is neither read nor
    waited for (what a buffer holds in its place is an earlier group's,
    or the zeros the buffers open with, and masked).  ``copies(i,
    block)``: the DMAs that bring pool block ``block`` to place ``i`` of
    a buffer; all of a buffer's count on one semaphore, so waiting takes
    the same walk.  (A loop, not ``group`` branches: every function that
    holds the step lowers the kernel's body anew, and unrolled at four
    places it took three times as long to lower: 20 s of a cell's
    set-up, PERF.md section 6, PR 50.)"""
    def one(i, _):
        for cp in copies(i, tab_ref[tile, at + i]):
            do(cp)

    jax.lax.fori_loop(0, jax.lax.min(last - at + 1, jnp.int32(group)), one,
                      None)


def fetch_ahead(fetch, t, nt, g, groups, slot):
    """Group ``g`` of tile ``t`` of ``nt`` arrived in buffer ``slot``,
    and the next one's blocks (the next tile's first group, behind this
    tile's last of ``groups``) on their way into the other buffer while
    this one is attended.  ``fetch(do, tile, g, slot)``: ``do`` the DMAs
    of group ``g`` of ``tile`` into buffer ``slot``
    (``each_group_block``); the grid's first is started by the kernel
    when it opens."""
    @pl.when(g + 1 < groups)
    def _():
        fetch(_start, t, g + 1, 1 - slot)

    @pl.when((g + 1 == groups) & (t + 1 < nt))
    def _():
        fetch(_start, t + 1, 0, 1 - slot)

    fetch(_wait, t, g, slot)


def _kernel(tab_ref, row_ref, pos_ref, len_ref, base_ref, q_ref, kv_ref,
            *rest, height: int, block_size: int, scale: float,
            num_kv_heads: int, rep: int, alibi: bool, kv_quant: bool,
            window, group: int):
    # optional inputs (order: the blocks' scales, alibi slopes) sit
    # between the pool and the aliased output; the scales' buffers are
    # the last scratch
    rest = list(rest)
    ks_ref = rest.pop(0) if kv_quant else None
    slopes_ref = rest.pop(0) if alibi else None
    (_, o_ref, qs_ref, ob_ref, acc_ref, m_ref, l_ref, par_ref, sem, sem_in,
     buf_ref, *sbuf_ref) = rest
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    R = height * rep
    keys = group * block_size
    D = buf_ref.shape[-1]

    def fetch(do, tile, g, slot):
        """``do`` (start or wait) the DMAs of group ``g`` of ``tile``
        into buffer ``slot``: each needed block from the pool row where
        it lies (the stack's: the layer's base added), a quantized
        cache's scales from the same row of theirs.  A window layer's
        groups count from the first block its first query's window
        touches."""
        first, last = _tile_span(tile, pos_ref, len_ref, block_size, window)

        def copies(i, block):
            cps = [pltpu.make_async_copy(kv_ref.at[block + base_ref[0]],
                                         buf_ref.at[slot, i],
                                         sem_in.at[slot])]
            if kv_quant:
                cps.append(pltpu.make_async_copy(
                    ks_ref.at[block + base_ref[0]], sbuf_ref[0].at[slot, i],
                    sem_in.at[slot]))
            return cps

        each_group_block(do, tab_ref, tile, first + g * group, last, group,
                         copies)

    pos0 = pos_ref[t]
    first, last = _tile_span(t, pos_ref, len_ref, block_size, window)
    groups = jax.lax.div(last - first, jnp.int32(group)) + 1

    # the two buffers seen as their 32-bit sublanes, [2, keys * 2 * hw,
    # D], where a block has such a view (``of_group``)
    pack = 4 // buf_ref.dtype.itemsize      # heads a 32-bit sublane holds
    hw = num_kv_heads // pack
    words = None
    if not (num_kv_heads % pack or D % 128
            or buf_ref.dtype not in (jnp.float32, jnp.bfloat16, jnp.int8)):
        words = (buf_ref if pack == 1 else buf_ref.bitcast(jnp.uint32)
                 ).reshape(2, keys * 2 * hw, D)

    @pl.when(t == 0)
    def _():
        # (a masked key's value meets the second product too: zeros, not
        # whatever the buffers held)
        def zero(i, _):
            buf_ref[jax.lax.div(i, group), jax.lax.rem(i, group)] = jnp.zeros(
                buf_ref.shape[2:], buf_ref.dtype)

        jax.lax.fori_loop(0, 2 * group, zero, None)
        for ref in sbuf_ref:
            ref[...] = jnp.zeros_like(ref)
        par_ref[0] = 0
        fetch(_start, 0, 0, 0)

    # the buffer that holds this tile's first group: the one the tile
    # before left free, whose last step started these DMAs
    par = par_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # fold the tile's queries [height, H, D] to one [height * rep, D]
    # operand per kv head, once for all its groups (row = token * rep +
    # head of the group)
    for h in range(num_kv_heads):
        qs_ref[h] = (q_ref[:, h, :] if rep == 1 else
                     q_ref[:, h * rep:(h + 1) * rep, :].reshape(
                         R, q_ref.shape[-1]))
    # a folded row's position: row // rep tokens after the first
    qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep

    def each(part, axis=0):
        """``part(i)`` of the group's every block, one after another."""
        parts = [part(i) for i in range(group)]
        return parts[0] if group == 1 else jnp.concatenate(parts, axis=axis)

    def of_group(slot, c, h, dtype):
        """Keys (c = 0) or values (1) of kv head ``h`` in the group that
        buffer ``slot`` holds, [keys, D]."""
        if words is None:   # gathered a key at a time
            x = each(lambda i: buf_ref[slot, i, :, c, h, :])
        else:
            # in a buffer's words head h's sublanes lie ``2 * hw`` apart,
            # a key each and a block's behind the block's before it, so
            # one strided load brings the group's keys, eight an
            # instruction, where indexing the head brings one and
            # rotates it into place; a sublane holds ``pack`` heads of
            # one key, h's bits are shifted out
            x = words.at[slot][
                pl.ds(c * hw + h // pack, keys, stride=2 * hw), :]
            at = h % pack * (32 // pack)
            if buf_ref.dtype == jnp.bfloat16:   # the high half of an f32
                x = pltpu.bitcast(
                    x & jnp.uint32(0xFFFF0000) if at else x << 16,
                    jnp.float32).astype(jnp.bfloat16)
            elif buf_ref.dtype == jnp.int8:     # sign-extended
                x = pltpu.bitcast(x << (24 - at), jnp.int32) >> 24
        # (a quantized cache's codes are exact in ``dtype``; their
        # scales meet the scores and the weights, ``scales_of``)
        return x.astype(jnp.float32).astype(dtype) if kv_quant else x

    def scales_of(slot, c, h):
        """The scales of head ``h``'s keys (c = 0) or values (1) in the
        group that buffer ``slot`` holds, [1, keys] in the scores'
        column order: in-VMEM dequant, HBM only streamed the codes.  A
        block's scales are ``[Hkv, 2 * bs]`` (a head a row; its keys'
        then its values', ``KVCacheConfig.kv_zeros``), so head ``h``'s
        rows of the group are one strided load."""
        rows = sbuf_ref[0].at[slot].reshape(group * num_kv_heads,
                                            2 * block_size)[
            pl.ds(h, group, stride=num_kv_heads), :]
        return each(lambda i: rows[i:i + 1,
                                   c * block_size:(c + 1) * block_size],
                    axis=1)

    def attend(g, _):
        slot = jax.lax.rem(par + g, 2)
        fetch_ahead(fetch, t, nt, g, groups, slot)
        cols = (first + g * group) * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (R, keys), 1)
        # this also masks whole the blocks of the group that lie past the
        # tile's last position and were not read
        keep = cols <= qpos
        if window is not None:
            # a row whose window starts after this group is masked whole
            # here: what it then adds is wiped when its first real group
            # raises its maximum (the correction is exp(-1e30 - m) = 0)
            keep &= cols > qpos - window
        for h in range(num_kv_heads):          # static unroll (GQA groups)
            q = qs_ref[h]                                  # [R, D]
            k = of_group(slot, 0, h, q.dtype)
            v = of_group(slot, 1, h, q.dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [R, keys]
            if kv_quant:    # q . (code * scale) = (q . code) * scale
                s = s * scales_of(slot, 0, h)
            if alibi:       # ALiBi: slope_h * absolute key position
                s = s + slopes_ref[h] * cols.astype(jnp.float32)
            s = jnp.where(keep, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_prev * corr + p.sum(axis=1, keepdims=True)
            if kv_quant:
                p = p * scales_of(slot, 1, h)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [R, D]
            acc_ref[h] = acc_ref[h] * corr + pv
        return 0

    jax.lax.fori_loop(0, groups, attend, 0)
    par_ref[0] = jax.lax.rem(par + groups, 2)

    def fill(ob):
        for h in range(num_kv_heads):
            # unfolded in f32: Mosaic has no such shape cast for packed
            # rows narrower than a lane tile (gpt2's D = 64 in bf16)
            o = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).reshape(
                height, rep, acc_ref.shape[-1])
            ob[:, h * rep:(h + 1) * rep, :o.shape[-1]] = o.astype(ob.dtype)

    send_tile_rows(t, nt, fill, ob_ref, o_ref, sem, row_ref, len_ref)


def _attend(tiles: TileList, kv_layer, kv_scales, q, out, base, height: int,
            scale: float, slopes, window=None):
    """One ``pallas_call`` over ``tiles`` → ``out`` with their rows
    written (``out`` is donated to the call and returned)."""
    T, H, D = q.shape
    _, bs, _, Hkv, _ = kv_layer.shape
    rep = H // Hkv
    R = height * rep
    kv_quant = kv_scales is not None
    group = kv_group(height, rep, Hkv, D, bs, kv_layer.dtype,
                     tiles.tables.shape[1], kv_quant)
    alibi = slopes is not None
    prefetch = [tiles.tables, tiles.row, tiles.pos, tiles.length,
                jnp.reshape(base, (1,)).astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((pl.Element(height), pl.Element(H), pl.Element(D)),
                     lambda t, tables, row, *_: (row[t], 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, kv_layer]
    scratch = [
        pltpu.VMEM((Hkv, R, D), q.dtype),            # folded queries
        pltpu.VMEM((2, height) + out.shape[1:], q.dtype),  # output rows
        pltpu.VMEM((Hkv, R, D), jnp.float32),
        pltpu.VMEM((Hkv, R, 1), jnp.float32),
        pltpu.VMEM((Hkv, R, 1), jnp.float32),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((2, group, bs, 2, Hkv, D), kv_layer.dtype)]  # two groups
    if kv_quant:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(kv_scales)
        scratch.append(pltpu.VMEM((2, group) + kv_scales.shape[1:],
                                  jnp.float32))
    if alibi:
        # per folded row (token * rep + head of the group)
        in_specs.append(pl.BlockSpec((Hkv, R, 1), lambda t, *_: (0, 0, 0)))
        operands.append(jnp.tile(
            jnp.asarray(slopes, jnp.float32).reshape(Hkv, 1, rep),
            (1, height, 1)).reshape(Hkv, R, 1))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(out)

    return pl.pallas_call(
        functools.partial(_kernel, height=height, block_size=bs,
                          scale=scale, num_kv_heads=Hkv, rep=rep,
                          alibi=alibi, kv_quant=kv_quant, window=window,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles.count,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={len(prefetch) + len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_use_interpret(),
        name=f"paged_attention{'' if window is None else '_w'}_h{height}",
    )(*prefetch, *operands)


def paged_attention(kv_layer, q, tiles: QueryTiles, scale: float,
                    slopes=None, layer=None, window=None):
    """kv_layer: [blocks+1, bs, 2, Hkv, D] (last row = trash), or a
    (data, scales) tuple for a quantized cache (scales
    [blocks+1, Hkv, 2 * bs] f32, a head's keys' then its values';
    codes dequantized in VMEM so HBM only streams the 1-byte payloads);
    q: [T, H, D]; ``tiles``: ``query_tiles`` of the step → out
    [T, H, D], zero in the rows of no tile (budget padding).
    ``slopes``: optional ALiBi per-head slopes, any shape reshapeable to
    [Hkv, rep] in head order h = hkv*rep + r (reference analog: the alibi
    operand of the inference softmax kernels, csrc/transformer/inference/
    csrc/softmax.cu).
    ``layer``: ``(base, rows)`` when ``kv_layer`` is the stacked cache
    viewed as ``[L * rows, ...]`` and this call attends layer ``li``,
    whose ``rows`` rows (its blocks, then its trash row) start at row
    ``base = li * rows``; ``None`` is a pool of one layer.  The call
    needs only the offset: the kernel copies row ``base + block`` of the
    codes, and of the scales, as it copies any row, from where it lies;
    nothing of the pool is cut, padded or relaid for the call.
    ``window``: a window layer's window (module docstring).

    A DMA moves whole memory tiles, so where Mosaic compiles the kernel
    a block's ``(Hkv, D)`` slab has to fill them (``slab_rows`` rows of
    128 lanes) and a block's scales ``[Hkv, 2 * bs]`` theirs: a pool
    that the kernel reads is allocated so (``KVCacheConfig.tiled``: the
    heads and lanes a model lacks are zeros, and so are the queries'),
    never filled up for a call.  (The interpreter copies any shape.)"""
    kv_scales = None
    if isinstance(kv_layer, tuple):
        kv_layer, kv_scales = kv_layer
    base = 0 if layer is None else layer[0]
    T, H, D = q.shape
    # the element-offset query window of a tile that starts in the last
    # rows reads past them: give it rows to read
    qp = jnp.pad(q, ((0, LONG), (0, 0), (0, 0)))
    # the output's rows leave by DMA too: heads that do not fill their
    # sublanes get an output that does, cut back here
    out = jnp.zeros((T, H if H < 8 else _pad(H, 8), _pad(D, 128)), q.dtype)
    for tl, height in ((tiles.long, LONG), (tiles.short, SHORT)):
        out = _attend(tl, kv_layer, kv_scales, qp, out, base, height,
                      scale, slopes, window)
    return out[:, :H, :D]
