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

* grid ``(tiles, groups)``, both traced: a step runs as many grid rows
  as it has tiles, each as long as the deepest tile's context in
  *groups* of ``k`` consecutive KV blocks (the compiled bucket
  ``max_blocks_per_seq`` only bounds it).  One grid step attends one
  tile (all heads) to one group, ``k * block_size`` keys: per kv head
  ONE score product, mask, online-softmax update and value product, so
  the serial chain between them (a few hundred cycles whatever the
  width) is paid once a group and not once a block.  ``k`` is a static
  function of the call's shapes (``kv_group``: 8 for the decode tokens'
  call at 4 and 8 kv heads of 128, 4 at 16; 1 for a 512-row prefill
  tile, whose products already fill the step) and nothing selects it;
* the group's blocks are fetched block by block: the pool is handed to
  the call ``k`` times, one BlockSpec a block of the group, each block
  carrying every kv head so the trailing block dims are full-size (a
  Mosaic tiling requirement).  The tiles' tables (laid out by grid
  step, below), first rows, first positions and lengths ride scalar
  prefetch (``PrefetchScalarGridSpec``): a BlockSpec's index map picks
  the DMA'd block, the query's picks the tile's first row as an
  element offset into ``[T, H, D]`` — paged indirection and ragged rows
  both happen in the DMA engine, never as a gather;
* per kv head the products are ``[height * rep, D] x [D, k * bs]`` and
  ``[height * rep, k * bs] x [k * bs, D]``: the tile's queries are
  folded to that shape once, at its first group, and kept in VMEM; the
  causal mask is ``col <= first_pos + row``; the online softmax keeps
  (m, l, acc) per row in f32 across the tile's groups;
* a head's keys are read through the block's 32-bit sublanes: in VMEM a
  block is ``bs * 2 * Hkv`` rows of ``D`` lanes, a sublane holding two
  heads in bf16 (four in int8), so head ``h``'s 64 keys are eight
  strided loads and a shift where indexing ``[:, c, h, :]`` is 64 loads,
  64 rotates and 56 selects (that gather, not the chain, was all of a
  block's 1.2 us at 8 kv heads and 2.7 us at 16).  Shapes that have no
  such view (an odd head count a sublane, ``D`` under 128 lanes, fp8)
  are gathered as before;
* groups past the tile's last position are skipped (``pl.when``); a
  block of a group past it is masked whole and NOT read: its operand's
  index map stays on the block the operand already holds (the last one
  it showed for this tile, or the row an earlier tile left it on, or
  the first row a later tile will ask of it), so nothing is DMA'd for
  it.  A call reads the blocks its tiles need and, when the grid opens,
  at most one block for each operand that no tile of the list needs at
  all.  Which pool row each operand shows at each grid step is laid out
  as a table (``_group_rows``) that the index maps look up: once a
  step and outside the layer scan where the caller asks for it with
  the tiles (``group_tiles``), else in the call;
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
``ceil((W + height) / block_size) + 1`` blocks whatever the context
(its groups count from that first block, which need not be a multiple
of ``k``), and the mask cuts inside the first of them.  Those calls are named
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


# A grid step of a tile attends a GROUP of consecutive KV blocks.  How many
# is a static function of what a call can see (``kv_group``): the largest
# power of two, at most ``GROUP_MAX`` and the table's width, whose double
# buffers (as Mosaic tiles them in VMEM) and f32 score tile fit
# ``GROUP_VMEM_BYTES`` (half of Mosaic's default scoped VMEM), the score
# tile alone ``GROUP_SCORE_BYTES`` (half the vector registers: what a
# step's softmax keeps live)
GROUP_MAX = 8
GROUP_VMEM_BYTES = 8 * 1024 * 1024
GROUP_SCORE_BYTES = 128 * 1024


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
    rows: jnp.ndarray = None    # [n, steps * k] i32 ``tables`` laid out by
                                # the grid steps of one kind of layer's
                                # call (``group_tiles``); None: the call
                                # lays them out itself


class QueryTiles(NamedTuple):
    short: TileList
    long: TileList


def tile_counts(run_lengths: Sequence[int], short: int = SHORT,
                long: int = LONG) -> Tuple[int, int, int]:
    """(short tiles, long tiles, real rows in the long tiles) of a step
    whose runs have these lengths — the host's count of what
    ``query_tiles`` builds on the device at the same two heights."""
    n_short = sum(1 for n in run_lengths if 0 < n <= short)
    long_runs = [n for n in run_lengths if n > short]
    return n_short, sum(-(-n // long) for n in long_runs), sum(long_runs)


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def block_vmem_bytes(num_kv_heads: int, head_dim: int, block_size: int,
                     kv_dtype, quant: bool = False) -> int:
    """VMEM one ``[bs, 2, Hkv, D]`` KV block takes as Mosaic tiles it: the
    ``(Hkv, D)`` slab in memory tiles of 128 lanes by a power of two of
    rows, at least a 32-bit sublane's worth and at most 8 (4 kv heads in
    bf16 take no padding, 12 take 16, 64 lanes take 128); an int8
    cache's f32 scales ``[bs, 2, Hkv]`` with it, their heads padded to
    128 lanes."""
    item = jnp.dtype(kv_dtype).itemsize
    rows = 4 // item
    while rows < min(num_kv_heads, 8):
        rows *= 2
    block = block_size * 2 * _pad(num_kv_heads, rows) * _pad(
        head_dim, 128) * item
    if quant:
        block += block_size * 2 * _pad(num_kv_heads, 128) * 4
    return block


def group_vmem_bytes(group: int, rows: int, num_kv_heads: int, head_dim: int,
                     block_size: int, kv_dtype, quant: bool = False) -> int:
    """VMEM a grid step's group of ``group`` KV blocks takes: every
    block twice (double buffered, ``block_vmem_bytes``) and the f32
    score tile ``[rows, group * bs]``."""
    return (2 * group * block_vmem_bytes(num_kv_heads, head_dim, block_size,
                                         kv_dtype, quant)
            + rows * group * block_size * 4)


def kv_group(height: int, rep: int, num_kv_heads: int, head_dim: int,
             block_size: int, kv_dtype, table_blocks: int,
             quant: bool = False) -> int:
    """KV blocks a grid step of the call at ``height`` attends: what
    the kernel takes and what the host counts with (``group_steps``).
    Nothing but the call's own shapes decides it."""
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
    """(grid steps that hold at least one needed block, needed blocks)
    of one layer's short call over ``runs``, ``(first position, rows)``
    each, rows <= ``SHORT`` — the host's count of what the kernel's
    grid does for them (a window layer with ``window``)."""
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
    position ``pos`` with ``length`` rows has to read."""
    first = jnp.maximum(pos - (window - 1), 0) // block_size
    return first, (pos + jnp.maximum(length, 1) - 1) // block_size


def query_tiles(seq_slot, positions, token_valid, block_tables,
                block_size: int, max_blocks_per_seq: int,
                trash: int, window: int = None, short: int = SHORT,
                long: int = LONG) -> QueryTiles:
    """Cut a ragged batch into query tiles, on the device, once a step.

    seq_slot/positions: [T] i32, token_valid: [T] bool,
    block_tables: [max_seqs, max_blocks] i32 (-1 pad → row ``trash`` of
    a layer's pool).  A run is a maximal stretch of valid rows of one
    slot at consecutive positions; a slot holds at most one run a step
    (``StateManager.build_batch`` schedules a sequence once), which
    bounds the lists: ``max_seqs`` short tiles, ``T // long`` full long
    tiles and one partial one a long run.  ``window``: the model's
    attention window where it has window layers (``wblocks``).
    ``short``, ``long``: the two heights (this kernel's; the latent
    kernel of ``ops/mla.py`` cuts the same runs at its own)."""
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

    return QueryTiles(
        collect(first & is_short, run_len, min(T, max_seqs)),
        collect(valid & ~is_short & (off % long == 0),
                jnp.minimum(run_len - off, long),
                max(1, T // long + min(max_seqs, T // (short + 1)))))


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


def send_tile_rows(t, nt, fill, ob_ref, o_ref, sem, row_ref, len_ref):
    """A finished tile's rows out of the kernel by its own DMAs, double
    buffered: those of tile ``t`` of ``nt`` (the grid's row and rows,
    read outside any ``pl.when``) are made by ``fill(buffer)`` and start
    here; they are waited for when the next tile (or the grid) ends, so
    they overlap its blocks.  ``ob_ref``: ``[2, height, ...]`` in VMEM,
    ``o_ref`` the output in HBM, ``sem`` two DMA semaphores."""
    slot = t % 2
    start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

    @pl.when(t > 0)
    def _():
        _each_row_copy(wait, ob_ref.at[1 - slot], o_ref, sem.at[1 - slot],
                       row_ref[t - 1], len_ref[t - 1])

    fill(ob_ref.at[slot])
    mine = (ob_ref.at[slot], o_ref, sem.at[slot], row_ref[t], len_ref[t])
    _each_row_copy(start, *mine)

    @pl.when(t == nt - 1)
    def _():
        _each_row_copy(wait, *mine)


def _tile_span(t, pos, length, block_size: int, window):
    """(first, last) KV block tile ``t`` has to read; ``pos``/``length``
    are the tile list's (refs in an index map or the kernel, arrays in
    ``_group_rows``)."""
    if window is None:
        return 0, (pos[t] + jnp.maximum(length[t], 1) - 1) // block_size
    return window_blocks(pos[t], length[t], window, block_size)


def _kernel(rows_ref, row_ref, pos_ref, len_ref, base_ref, *rest,
            height: int, block_size: int, scale: float,
            num_kv_heads: int, rep: int, alibi: bool, kv_quant: bool,
            window, group: int):
    # the group's blocks come one operand each; optional inputs (order:
    # the blocks' scales, alibi slopes) sit between them and the aliased
    # output
    rest = list(rest)
    q_ref = rest.pop(0)
    kv_refs = [rest.pop(0) for _ in range(group)]
    ks_refs = [rest.pop(0) for _ in range(group)] if kv_quant else None
    slopes_ref = rest.pop(0) if alibi else None
    _, o_ref, qs_ref, ob_ref, acc_ref, m_ref, l_ref, sem = rest
    t = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(0)
    ng = pl.num_programs(1)
    R = height * rep
    keys = group * block_size
    pos0 = pos_ref[t]
    n = len_ref[t]
    # the first KV block of the group this grid step attends: a window
    # layer's row starts at the first block its first query's window
    # touches
    blk = j * group + _tile_span(t, pos_ref, len_ref, block_size, window)[0]

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

    # a block seen as its 32-bit sublanes, [bs * 2 * hw, D], where it has
    # such a view (``of_group``; made once: a ref's bitcast is slow to
    # trace)
    pack = 4 // kv_refs[0].dtype.itemsize   # heads a 32-bit sublane holds
    hw = num_kv_heads // pack
    words = None
    if not (num_kv_heads % pack or kv_refs[0].shape[-1] % 128
            or kv_refs[0].dtype not in (jnp.float32, jnp.bfloat16, jnp.int8)):
        words = [(ref if pack == 1 else ref.bitcast(jnp.uint32)).reshape(
            block_size * 2 * hw, ref.shape[-1]) for ref in kv_refs]

    def each(part):
        """``part(i)`` of the group's every block, one after another."""
        parts = [part(i) for i in range(group)]
        return parts[0] if group == 1 else jnp.concatenate(parts, axis=0)

    def of_group(c, h, dtype):
        """Keys (c = 0) or values (1) of kv head ``h`` in the group's
        blocks, [keys, D]."""
        kv = kv_refs[0]
        if words is None:   # gathered a key at a time
            x = each(lambda i: kv_refs[i][0, :, c, h, :])
        else:
            # in a block's words head h's sublanes lie ``2 * hw`` apart,
            # a key each, so strided loads bring eight keys an
            # instruction where indexing the head brings one and rotates
            # it into place; a sublane holds ``pack`` heads of one key,
            # h's bits are shifted out (once for the whole group)
            x = each(lambda i: words[i][
                pl.ds(c * hw + h // pack, block_size, stride=2 * hw), :])
            at = h % pack * (32 // pack)
            if kv.dtype == jnp.bfloat16:    # the high half of an f32
                x = pltpu.bitcast(
                    x & jnp.uint32(0xFFFF0000) if at else x << 16,
                    jnp.float32).astype(jnp.bfloat16)
            elif kv.dtype == jnp.int8:      # sign-extended
                x = pltpu.bitcast(x << (24 - at), jnp.int32) >> 24
        if kv_quant:        # in-VMEM dequant: HBM only streamed codes
            x = (x.astype(jnp.float32) * each(
                lambda i: ks_refs[i][0, :, c, h][:, None])).astype(dtype)
        return x

    # the whole group is past the tile's last position → nothing to add
    @pl.when(blk * block_size <= pos0 + n - 1)
    def _compute():
        cols = blk * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (R, keys), 1)
        # a folded row's position: row // rep tokens after the first
        qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep
        # this also masks whole the blocks of the group that lie past the
        # tile's last position: their operands hold some earlier block
        keep = cols <= qpos
        if window is not None:
            # a row whose window starts after this group is masked whole
            # here: what it then adds is wiped when its first real group
            # raises its maximum (the correction is exp(-1e30 - m) = 0)
            keep &= cols > qpos - window
        for h in range(num_kv_heads):          # static unroll (GQA groups)
            q = qs_ref[h]                                  # [R, D]
            k = of_group(0, h, q.dtype)
            v = of_group(1, h, q.dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [R, keys]
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

    @pl.when(j == ng - 1)
    def _finalize():
        def fill(ob):
            for h in range(num_kv_heads):
                # unfolded in f32: Mosaic has no such shape cast for packed
                # rows narrower than a lane tile (gpt2's D = 64 in bf16)
                o = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).reshape(
                    height, rep, acc_ref.shape[-1])
                ob[:, h * rep:(h + 1) * rep, :o.shape[-1]] = o.astype(
                    ob.dtype)

        send_tile_rows(t, nt, fill, ob_ref, o_ref, sem, row_ref, len_ref)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _group_rows(tiles: TileList, group: int, block_size: int, window):
    """[n, steps * group] i32: the pool row the ``i``-th operand of a
    group of several shows at grid step ``(t, j)``, at
    ``[t, j * group + i]``; made once a call (and traced once a shape),
    so that an index map is one lookup.  While tile ``t`` needs it, that
    is block ``first + j * group + i`` of its table; past the tile's
    last block the operand stays on the last block it held for this
    tile: consecutive grid steps then revisit the same block and Pallas
    skips the DMA entirely (the kernel masks what it holds).  An operand
    the tile needs no block of at all (its span has ``i`` blocks or
    fewer) stays on the row it was left on by the last tile that needed
    it, so nothing is fetched, or, before any did, on the first row the
    next such tile will ask for (the fetch that opens the grid is then
    that tile's own)."""
    n, nb = tiles.tables.shape
    t = jnp.arange(n, dtype=jnp.int32)
    first, last = _tile_span(t, tiles.pos, tiles.length, block_size, window)
    span = (last - first)[:, None]
    slot = np.arange(_pad(nb, group), dtype=np.int32)[None, :]
    i = slot % group
    # the operand's last block of this tile, once the row is past it
    # (``group`` is a power of two: the mask rounds down to a multiple)
    stay = i + ((span - i) & -group)
    rows = jnp.take_along_axis(
        tiles.tables,
        jnp.clip(jnp.broadcast_to(first, (n,))[:, None]
                 + jnp.minimum(slot, stay), 0, nb - 1), axis=1)
    needs = (i[:, :group] <= span) & (t < tiles.count)[:, None]
    before = jax.lax.cummax(jnp.where(needs, t[:, None], -1), axis=0)
    before = jnp.concatenate(
        [jnp.full((1, group), -1, jnp.int32), before[:-1]])
    after = jax.lax.cummin(jnp.where(needs, t[:, None], n), axis=0,
                           reverse=True)
    # a tile's last step shows each operand's last block of it, its
    # first step the first
    left_on = jnp.take_along_axis(rows[:, -group:], jnp.maximum(before, 0),
                                  axis=0)
    opens_on = jnp.take_along_axis(rows[:, :group],
                                   jnp.minimum(after, n - 1), axis=0)
    held = jnp.where(before >= 0, left_on,
                     jnp.where(after < n, opens_on, tiles.tables[0, 0]))
    return jnp.where(i <= span, rows,
                     jnp.tile(held, (1, slot.shape[1] // group)))


def group_tiles(tiles: QueryTiles, rep: int, num_kv_heads: int,
                head_dim: int, block_size: int, kv_dtype,
                quant: bool = False, window: int = None) -> QueryTiles:
    """``tiles`` with each list's tables laid out by the grid steps of
    the call that will walk it (``rows``), for the layers of one kind
    (``window``: a window layer's) over a pool of these shapes (what
    ``kv_group`` reads; under ``shard_map`` a chip's own).  Made once a
    step, outside the layer scan, like the tiles themselves; a call
    whose tiles come without lays them out itself, every layer."""
    def laid(tl: TileList, height: int) -> TileList:
        k = kv_group(height, rep, num_kv_heads, head_dim, block_size,
                     kv_dtype, tl.tables.shape[1], quant)
        return tl._replace(rows=None if k == 1 else _group_rows(
            tl, k, block_size, window))

    return QueryTiles(laid(tiles.short, SHORT), laid(tiles.long, LONG))


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

    if group == 1:
        # the one operand has a block of every tile: the tile's table,
        # clamped to its last block in the map.  (An empty list's entry
        # 0 has length 0; whatever evaluates this for it must still get
        # a block of the table.)
        rows = tiles.tables

        def _at(i, t, j, pos, length):
            first, last = _tile_span(t, pos, length, bs, window)
            return jnp.minimum(first + j, last)
    else:
        rows = (_group_rows(tiles, group, bs, window)
                if tiles.rows is None else tiles.rows)
        assert rows.shape == (tiles.tables.shape[0],
                              _pad(tiles.tables.shape[1], group))

        def _at(i, t, j, pos, length):
            return j * group + i

    def _kv_index(i):
        return lambda t, j, rows, row, pos, length, base: (
            rows[t, _at(i, t, j, pos, length)] + base[0], 0, 0, 0, 0)

    def _ks_index(i):
        return lambda t, j, rows, row, pos, length, base: (
            rows[t, _at(i, t, j, pos, length)], 0, 0, 0)

    def _q_index(t, j, rows, row, *_):
        return (row[t], 0, 0)

    alibi = slopes is not None
    prefetch = [rows, tiles.row, tiles.pos, tiles.length,
                jnp.reshape(base, (1,)).astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((pl.Element(height), pl.Element(H), pl.Element(D)),
                     _q_index)]
    in_specs += [pl.BlockSpec((1, bs, 2, Hkv, D), _kv_index(i))
                 for i in range(group)]
    operands = [q] + [kv_layer] * group
    if kv_quant:
        in_specs += [pl.BlockSpec((1, bs, 2, Hkv), _ks_index(i))
                     for i in range(group)]
        operands += [kv_scales] * group
    if alibi:
        # per folded row (token * rep + head of the group)
        in_specs.append(pl.BlockSpec((Hkv, R, 1), lambda t, j, *_: (0, 0, 0)))
        operands.append(jnp.tile(
            jnp.asarray(slopes, jnp.float32).reshape(Hkv, 1, rep),
            (1, height, 1)).reshape(Hkv, R, 1))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(out)

    blocks = tiles.blocks if window is None else tiles.wblocks
    return pl.pallas_call(
        functools.partial(_kernel, height=height, block_size=bs,
                          scale=scale, num_kv_heads=Hkv, rep=rep,
                          alibi=alibi, kv_quant=kv_quant, window=window,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles.count, (blocks + group - 1) // group),
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
    q: [T, H, D]; ``tiles``: ``query_tiles`` of the step, or
    ``group_tiles`` of them for this kind of layer and these shapes
    where the caller has many layers → out [T, H, D], zero in the rows
    of no tile (budget padding).
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
    must have been cut, and laid out, with it."""
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
