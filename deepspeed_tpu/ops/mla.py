"""Latent attention (DeepSeek-V2's MLA; the query in one product, or
through a latent of its own as DeepSeek-V2's and V3's large models):
what a served ``"mla"`` layer needs.

A token leaves ONE vector in the cache, ``[c | k_r]``: the normed
key-value latent ``c`` (``kv_rank`` values) and the rotated shared key
``k_r`` (``rope_dim`` values).  Every head's keys and values are linear
in ``c`` (``[k_n | v] = c W_kvb``), so the scores and the output can be
taken over the cached vectors themselves with ``W_kvb`` folded into the
query and the output::

    s_h(t, j) = (q_n W_kb_h^T) . c_j + q_r . k_r_j     (over all 576)
    o_h(t)    = (sum_j p_h(t, j) c_j) W_vb_h            (the first 512)

which is attention of all the query heads over one shared key of
``kv_rank + rope_dim`` and one shared value of ``kv_rank``.

* :func:`rope_interleaved`: the rotary embedding over adjacent pairs;
* :func:`latent_write` (scope ``latent_write``): a step's rows into the
  latent pool by block table;
* :func:`latent_attend` (scope ``latent_attn``), the XLA formulation
  (``attn_impl="xla"``: what a step lowered for the CPU runs): groups of
  query rows, each group of one sequence, over that sequence's cached
  rows read by block table, a few blocks at a time under an online
  softmax.  The serving forward calls it twice: the step's one-token
  rows, one group a slot, and the chunks of its longer runs;
* :func:`latent_attend_tiles` (the same scope), the Pallas kernel
  (``attn_impl="pallas"``: what a TPU runs), below, and
  :func:`latent_attend_expanded`, its third call, for the runs long
  enough to pay for ``c W_kvb`` (further below);
* :func:`attention_forward`: the layer over whole sequences with keys
  and values expanded (``models/transformer.apply``).

The kernel does for a pool of latent rows what ``ops/paged_attention.py``
does for keys and values, in that kernel's form and on that module's
parts (the runs cut once a step by ``query_tiles``; the lists' tables,
first rows, first positions and lengths by scalar prefetch; the walk
that starts and waits for a group's needed blocks,
``each_group_block``; a finished tile's rows written by the kernel's
own DMAs, ``send_tile_rows``), with a body of its own:

* grid ``(tiles,)``, traced.  A tile is ``height`` rows of ONE run at
  ALL heads, its queries one ``[height * H, width]`` operand (a view of
  the tile's ``[height, H, width]`` window of ``q``, fetched once a
  tile).  It walks its OWN sequence's blocks in *groups* of ``k``
  consecutive blocks and no further: a one-token row at 1.5k cached rows
  costs 1.5k rows whatever the step's longest context;
* the blocks are fetched by the kernel's own DMAs, one a block, from the
  stacked pool ``[L * rows, bs, width]`` where it lies (the table's
  entry plus the layer's base; never gathered) into one of two VMEM
  buffers that hold a group's rows one block behind another, so the
  products read the buffer as it is.  While a group is attended the
  next one's blocks are on their way, and behind a tile's last group
  the NEXT tile's first: the DMA queue does not drain between tiles.  A
  block behind the tile's last position is not read.  (This form was
  measured here first, against a BlockSpec pipeline with an operand a
  block of the group, whose bookkeeping cost 0.17 us a block a grid
  step where the block's DMA takes 0.10, PERF.md section 6, PR 50; the
  paged kernel took it over in PR 51);
* one block in VMEM serves both products: the key is the block's whole
  row (``[c | k_r]`` and the zeros behind it), the value its first
  ``kv_rank`` lanes: one DMA a block, no second operand;
* scores, the softmax statistics (m, l) and the accumulator
  ``[height * H, kv_rank]`` are float32 and stay in VMEM across the
  tile's groups; ``p`` meets the rows in the pool's type, as
  ``latent_attend`` casts it; only a tile's finished rows are written;
* two heights, a property of the batch as ``SHORT``/``LONG`` are there
  (``tile_heights``): a one-token run is a tile of one row (``H`` rows
  for the MXU), a longer run is cut into tiles whose ``height * H`` is
  ``RUN_ROWS`` (32 rows at 32 heads, 16 at 64, 8 at 128, the least a
  tile may be).  ``k`` is ``latent_group``'s: 16 blocks for a one-token
  tile, 8 for a run's.  Both are static functions of what the call can
  see; nothing selects them.
* what binds each call follows from the heads.  A cached row costs the
  folded form ``2 H (row + kv_rank)`` operations and ``2 row`` bytes as
  counted (1,152; 1,280 as the pool stores it): 60 operations a byte at
  32 heads, 121 at 64, 242 at 128, where a v5e's 197 TF/s over 819 GB/s
  is 240.  So the one-token call is bound by its rows' bytes up to 64
  heads and sits on the ridge at 128 (DeepSeek-V2's; it read 52% of the
  larger of the two there: PERF.md section 6, PR 56), and a run's call,
  whose tile reads a row once for ``height`` queries, is compute-bound
  at every head count (three quarters of the peak in the folded form's
  own operations at 64 and at 128 heads).  ``MLADims.scale`` carries
  what a position scaling multiplies the softmax scale by (YaRN's
  ``m(mscale_all_dim)^2``); the rotated 64 of the query and of the
  shared key arrive rotated, at whatever frequencies the table holds.

**The third call: the expanded form** (``latent_attend_expanded``,
kernel ``latent_attention_h512``; PERF.md section 6, PR 58, which
holds the chip's readings quoted below).  The folded
form is right for a run that meets a cached row once or a few times and
wrong for a run of hundreds of rows: as executed it costs every head
``2 (640 + 512)`` operations a (query row, cached row) pair where
per-head keys of ``nope + rope`` (128 + 64, met as 256 lanes) and values
of 128 cost ``2 (256 + 128)``, a third, and the expansion ``[k_n | v] =
c W_kvb`` of a cached row (``2 x 512 x 256`` a head) is paid ONCE for
all the run's rows.  The same mathematics, reassociated (``(q_n W_kb^T)
. c = q_n . (c W_kb)``), nothing lowered: products in the pool's type
with float32 accumulation, float32 scores and statistics, ``p`` in the
pool's type, the shared rotated 64 read from the cached row as it lies.

* which run takes it is the code's choice from the run's length and
  ``MLADims`` alone (``expand_from``): by operations the two forms
  cross at ``kv_rank (nope + value) / (row + kv_rank - nope - rope -
  value)`` = 171 rows whatever the head count; measured on the chip at
  128 heads behind 16,384 cached rows the crossing lies at about 200
  rows (folded 6.1 ms against 6.8 at 176 rows, 7.0 against 6.8 at 208,
  7.5 against 6.6 at 224) and at 64 heads behind 4,096 at about 224,
  because the expansion runs at 82% of the MXU's peak and the pairs'
  products at 64% where the folded form's run at 76%, and because XLA
  lays the step's queries out head-major around the call (0.15 ms a
  layer whatever the run).  ``EXPAND_MARGIN`` = 1.3 puts the threshold
  at 224 rows; a run one row under it takes the folded run call, whose
  code is what it was.  ``query_tiles(wide=...)`` cuts such runs into a
  third list for this caller only;
* a tile is the rows of one run inside a window of ``WIDE`` = 512 rows
  of the batch, at ``wide_heads`` = 16 heads: grid ``(tiles, H / 16)``.
  The expansion is paid once a (tile, cached row, head), never once a
  row-tile: at the folded call's tile of 8 rows it would cost 21 times
  what it saves.  The step's queries arrive head-major ``[H, T, 256]``
  (one transposing pass by XLA; a tile's window is a BlockSpec block at
  any offset of the run in it), the result leaves as ``[tiles, H, 512,
  value]`` and XLA selects each row's from its tile: the heads' VALUES,
  no ``unfold_output`` for these rows.  Every head group walks the
  tile's blocks again (the same tables, ``each_group_block``,
  ``fetch_ahead``, two VMEM buffers, one DMA a block from the stacked
  pool): the cached rows are read ``H / 16`` times a run, 173 MB a
  layer at 16.9k rows, behind the products;
* per group of ``WIDE_KEYS`` = 1,024 cached rows and per head: ``[k_n |
  v] = c W_kvb_h`` (the head's ``[512, 256]`` of the matrix, brought by
  a DMA of its own when the grid step opens) into VMEM as the pool's
  type, then the run's rows ``WIDE_ROWS`` = 256 at a time (128 at either
  end of the run where it covers no more of a row-tile; a row-tile
  wholly above the group's keys is skipped) under the online softmax,
  ``PAIR`` = 2 heads a trip of the loop so that one head's softmax lies
  under the other's products.  Measured at 128 heads, 512 rows behind
  8,192: 5.3 ms against the folded call's 8.7 (with its two products
  around it); groups of 512 rows 6.0, of 256 rows 9.7; one head a trip
  7.1, four 5.5 (and twice the kernel's compile time); row-tiles of 128
  7.9, of 512 5.1 (but 9.1 against 8.6 for a run of 288 rows).  Leaving
  the mask off below the diagonal or folding the scale into the query
  moved nothing: what binds is the chain product - softmax - product of
  a row-tile, not the vector unit's count;
* VMEM at 16 heads and 512 rows: the queries' block 4 MB twice, the
  result's 2 MB twice, ``W_kvb``'s slice 4 MB, the accumulator 4 MB, (m,
  l) 4 MB each as Mosaic pads a ``[., 1]`` column, two groups of rows
  2.6 MB, two heads' keys and values 1.5 MB, two score tiles 2 MB:
  inside the 64 MiB the call scopes.  Expanded rows never go to HBM;
* at a row count with a third list the step's rows go through
  ``latent_attend_runs``, which makes the folded form's own products
  (``fold_query``, the padded query, the zeroed result,
  ``unfold_output``) for the rows that take that form: the one-token
  rows gathered, the run call under a ``cond`` on its list's count;
* the kernels' bodies and index maps use the primitives where jnp
  would keep a body (``jax.lax.div`` and ``rem``, not ``//`` and ``%``;
  ``jax.lax.select``, not ``jnp.where``): jnp keeps a jitted function's
  traced body (``where``, and the divisions through it) as its FIRST
  caller in the process left it, locations and all, and a kernel's
  serialised module travels in the step program, so in the program's key
  in the compile cache.  An index map that wrote ``row[t] // wide``
  carried ten frames of a shallow caller (the engine's step function,
  the thread that compiled it) into the expanded call's module, the key
  then depended on which thread traced first, and a warm set-up of
  ``serve-mla-docqa`` compiled four programs again (PERF.md section 6,
  PR 58).  The folded calls are reached by two paths since
  (``latent_attend_tiles`` at a row count without a third list,
  ``latent_attend_runs`` with one), traced side by side on an engine's
  threads: their kernel spells its mask and its four scalar divisions
  with the primitives too, the same arithmetic
  (``tests/test_tpu_compile.py`` lowers a step in two processes of
  their own, in either order of its row counts, and holds the kernels'
  locations to this package's files and their modules to the same
  bytes).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (GROUP_MAX, GROUP_SCORE_BYTES, GROUP_VMEM_BYTES,
                              LONG, NEG_INF, QueryTiles, TileList, _start, _wait,
                              _use_interpret, each_group_block, fetch_ahead,
                              query_tiles, send_tile_rows)

F32 = jnp.float32
# MXU rows (query rows x heads) of a tile of a run of several tokens
RUN_ROWS = 1024
# the expanded form's tile (module docstring: each was measured): a
# window's rows of the batch, the heads of a tile, the rows that meet a
# group's keys at a time, the cached rows a group holds (expanded whole),
# the heads a trip of the loop
WIDE, WIDE_HEADS, WIDE_ROWS, WIDE_KEYS, PAIR = 512, 16, 256, 1024, 2
# what the expanded form's break-even in rows is multiplied by
EXPAND_MARGIN = 1.3
NEVER = 1 << 30


class MLADims(NamedTuple):
    """The layer's sizes (``TransformerConfig.mla_dims``)."""
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    # the query latent's size (``q = N_q(h W_qa) W_qb``); 0: ``h W_q``
    q_rank: int = 0
    # constant multipliers on the normed query latent and on the normed
    # key-value latent (1.0 multiplies nothing)
    q_scale: float = 1.0
    kv_scale: float = 1.0
    # a multiplier on the softmax scale (YaRN's ``m(mscale_all_dim)^2``,
    # ``models/layers.Yarn.score_scale``)
    score_scale: float = 1.0

    @property
    def row(self) -> int:
        """What a token leaves in the cache: ``[c | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5 * self.score_scale


def rope_interleaved(x, cos, sin, positions):
    """Rotate adjacent pairs ``(x0, x1), (x2, x3), ...`` of the last
    axis.  x: [T, ..., R]; cos, sin: [max, R / 2]; positions: [T]."""
    c, s = cos[positions], sin[positions]                      # [T, R/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (c.shape[-1],)
    c, s = c.reshape(shape), s.reshape(shape)
    x32 = x.astype(F32).reshape(x.shape[:-1] + (-1, 2))
    a, b = x32[..., 0], x32[..., 1]
    out = jnp.stack([a * c - b * s, b * c + a * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _widened(x, width: int):
    """``x``'s last axis filled with zeros up to ``width``: the pool's
    rows are whole vectors of 128 lanes (``KVCacheConfig.latent_row``)."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, width - x.shape[-1]),))


def latent_write(pool, rows, blk, off):
    """``rows [T, row]`` into ``pool [blocks, bs, >= row]`` at block
    ``blk`` [T] (a row that pads the step: the layer's trash block) and
    offset ``off`` [T]."""
    return pool.at[blk, off].set(
        _widened(rows.astype(pool.dtype), pool.shape[-1]))


def latent_attend(pool, q, qpos, tables, dims: MLADims, blocks: int):
    """Query groups over their sequences' cached rows.

    pool: [rows, bs, >= row] (zeros behind a row's values); q: [G, R, H,
    row] folded queries (the stored type); qpos: [G, R] i32, a query's
    position (-1: the row is not
    there); tables: [G, nb] i32, rows of ``pool`` that hold the group's
    sequence, block by block (a block the sequence does not have: any
    row, it is masked).  ``blocks``: blocks read in one pass of the loop,
    which stops behind the last block any query reads.
    → [G, R, H, kv_rank] float32 (a row that is not there: garbage)."""
    G, R, H, _ = q.shape
    q = _widened(q, pool.shape[-1])
    bs = pool.shape[1]
    span = blocks * bs
    nb = tables.shape[1]
    passes = -(-nb // blocks)
    tables = jnp.pad(tables, ((0, 0), (0, passes * blocks - nb)))
    need = (jnp.max(qpos) + span) // span          # passes that hold a key

    def one(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, j * blocks, blocks, 1)
        ctx = pool[ids].reshape(G, span, -1)                # [G, span, row]
        s = jnp.einsum("grhd,gcd->grhc", q, ctx,
                       preferred_element_type=F32) * dims.scale
        cols = j * span + jnp.arange(span)
        s = jnp.where((cols[None, None, :] <= qpos[:, :, None])[:, :, None],
                      s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        w = jnp.exp(m - m_new)
        pv = jnp.einsum("grhc,gcd->grhd", p.astype(ctx.dtype),
                        ctx[..., :dims.kv_rank], preferred_element_type=F32)
        return m_new, l * w + p.sum(-1), acc * w[..., None] + pv

    init = (jnp.full((G, R, H), -1e30, F32), jnp.zeros((G, R, H), F32),
            jnp.zeros((G, R, H, dims.kv_rank), F32))
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(need, passes), one, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def tile_heights(heads: int) -> Tuple[int, int]:
    """(rows of a one-token run's tile, rows of a tile of a longer
    run) at ``heads`` query heads."""
    return 1, max(8, min(LONG, RUN_ROWS // heads))


def latent_group(rows: int, width: int, block_size: int, dtype,
                 table_blocks: int) -> int:
    """Blocks a group of a tile of ``rows`` MXU rows holds: the largest
    power of two, at most ``GROUP_MAX`` and the table's width, whose
    float32 score tile is at most ``GROUP_SCORE_BYTES`` and fits
    ``GROUP_VMEM_BYTES`` with the two buffers (the paged kernel's
    budgets, ``ops/paged_attention``: 16 blocks, 2 MiB, 16 MiB).
    More blocks a group are fewer passes of the loop and more DMAs in
    flight (what a one-token tile wants: 16); a tile of a thousand rows
    rescales a 2 MB accumulator a group and wastes what a last group
    holds too much of (8)."""
    block = block_size * width * jnp.dtype(dtype).itemsize
    k = 1
    while (2 * k <= min(GROUP_MAX, table_blocks)
           and rows * 2 * k * block_size * 4 <= GROUP_SCORE_BYTES
           and 2 * 2 * k * block + rows * 2 * k * block_size * 4
           <= GROUP_VMEM_BYTES):
        k *= 2
    return k


def expand_from(dims: MLADims) -> int:
    """Rows from which a run attends in the expanded form.  A (query
    row, cached row) pair costs a head ``2 (row + kv_rank)`` operations
    folded and ``2 (nope + rope + value)`` expanded, and a cached row
    costs a head ``2 kv_rank (nope + value)`` to expand, once for all
    the run's rows: the two forms cross at ``kv_rank (nope + value) /
    (row + kv_rank - nope - rope - value)`` rows, 171 at DeepSeek-V2's
    sizes whatever the head count, times ``EXPAND_MARGIN`` for what the
    count of operations leaves out (module docstring), rounded up to
    whole vectors of 8 rows.  Sizes at which the expanded pair costs no
    less (a latent no larger than a head's key and value): ``NEVER``,
    more rows than a step holds."""
    per_pair = (dims.row + dims.kv_rank
                - dims.nope_dim - dims.rope_dim - dims.value_dim)
    if per_pair <= 0:
        return NEVER
    rows = dims.kv_rank * (dims.nope_dim + dims.value_dim) / per_pair
    return -(-int(rows * EXPAND_MARGIN + 0.5) // 8) * 8


def wide_cut(dims: MLADims) -> Tuple[int, int]:
    """``query_tiles``' ``wide`` for this layer: the runs of at least
    ``expand_from(dims)`` rows, in windows of ``WIDE`` rows (what the
    step's tiles are cut with and what the host counts with)."""
    return expand_from(dims), WIDE


def wide_heads(dims: MLADims) -> int:
    """Heads a tile of the expanded form holds: the largest divisor of
    the heads that is at most ``WIDE_HEADS``."""
    return max(g for g in range(1, WIDE_HEADS + 1) if dims.heads % g == 0)


def latent_tiles(seq_slot, positions, token_valid, block_tables,
                 block_size: int, max_blocks_per_seq: int, trash: int,
                 heads: int, heights: Tuple[int, int] = None,
                 wide: Tuple[int, int] = None) -> QueryTiles:
    """A step's runs cut into the kernel's tiles (``query_tiles`` at
    ``tile_heights(heads)``: ``short`` holds the one-token runs,
    ``long`` the tiles of the longer ones), once a step and outside the
    layer scan.  ``trash``: the pool row of a layer's trash block.
    ``heights``: other heights than the kernel's own, for a test of
    small tiles.  ``wide``: ``wide_cut(dims)`` where the
    caller has the expanded form's call for the runs of at least so many
    rows (``latent_attend_expanded``), which then leave ``long``."""
    short, long = heights or tile_heights(heads)
    return query_tiles(seq_slot, positions, token_valid, block_tables,
                       block_size, max_blocks_per_seq, trash,
                       short=short, long=long, wide=wide)


def _tile_kernel(tab_ref, row_ref, pos_ref, len_ref, base_ref, q_ref,
                 pool_ref, _, o_ref, ob_ref, buf_ref, acc_ref, m_ref, l_ref,
                 par_ref, sem, sem_in, *, height: int, heads: int,
                 block_size: int, scale: float, kv_rank: int, group: int):
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    R = height * heads
    keys = group * block_size

    def last_block(tile):
        return jax.lax.div(
            pos_ref[tile] + jax.lax.max(len_ref[tile], jnp.int32(1)) - 1,
            jnp.int32(block_size))

    def each_block(do, tile, g, slot):
        """``do`` (start or wait) the DMA of every block of group ``g``
        of ``tile`` that the tile needs, into buffer ``slot`` where the
        group's rows lie one block behind another (the paged kernel's
        walk: ``each_group_block``)."""
        def copies(i, block):
            return (pltpu.make_async_copy(
                pool_ref.at[block + base_ref[0]],
                buf_ref.at[slot, pl.ds(pl.multiple_of(i * block_size,
                                                      block_size),
                                       block_size)],
                sem_in.at[slot]),)

        last = last_block(tile)
        each_group_block(do, tab_ref, tile, g * group, last, group, copies)

    pos0 = pos_ref[t]
    groups = jax.lax.div(last_block(t), jnp.int32(group)) + 1

    @pl.when(t == 0)
    def _():
        # (masked rows meet the second product too: zeros, not whatever
        # the buffers held)
        buf_ref[...] = jnp.zeros_like(buf_ref)
        par_ref[0] = 0
        each_block(_start, 0, 0, 0)

    # the buffer that holds this tile's first group: the one the tile
    # before left free, whose last step started these DMAs
    par = par_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # row = token * heads + head
    q = q_ref[...].reshape(R, q_ref.shape[-1])
    qpos = pos0 + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0), jnp.int32(heads))

    def attend(g, _):
        slot = jax.lax.rem(par + g, 2)
        fetch_ahead(each_block, t, nt, g, groups, slot)
        ctx = buf_ref[slot]                                  # [keys, width]
        s = jax.lax.dot_general(
            q, ctx, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=F32) * scale               # [R, keys]
        cols = g * keys + jax.lax.broadcasted_iota(jnp.int32, (R, keys), 1)
        # this also masks whole the blocks of the group that lie past
        # the tile's last position and were not read
        s = jax.lax.select(cols <= qpos, s, jnp.full_like(s, NEG_INF))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        # the value is the row's first kv_rank lanes, where it lies
        pv = jax.lax.dot_general(
            p.astype(ctx.dtype), ctx[:, :kv_rank],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=F32)                       # [R, kv_rank]
        acc_ref[...] = acc_ref[...] * corr + pv
        return 0

    jax.lax.fori_loop(0, groups, attend, 0)
    par_ref[0] = jax.lax.rem(par + groups, 2)

    def fill(ob):
        ob[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                   ).reshape(ob.shape).astype(ob.dtype)

    send_tile_rows(t, nt, fill, ob_ref, o_ref, sem, row_ref, len_ref)


def _attend_tiles(tiles: TileList, pool, q, out, base, height: int,
                  dims: MLADims):
    """One ``pallas_call`` over ``tiles`` → ``out`` with their rows
    written (``out`` is donated to the call and returned)."""
    _, H, W = q.shape
    bs = pool.shape[1]
    R = height * H
    group = latent_group(R, W, bs, pool.dtype, tiles.tables.shape[1])
    prefetch = [tiles.tables, tiles.row, tiles.pos, tiles.length,
                jnp.reshape(base, (1,)).astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((pl.Element(height), pl.Element(H), pl.Element(W)),
                     lambda t, tables, row, *_: (row[t], 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY)]
    return pl.pallas_call(
        functools.partial(_tile_kernel, height=height, heads=H,
                          block_size=bs, scale=dims.scale,
                          kv_rank=dims.kv_rank, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles.count,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, height) + out.shape[1:], out.dtype),
                pltpu.VMEM((2, group * bs, W), pool.dtype),  # two groups
                pltpu.VMEM((R, dims.kv_rank), F32),
                pltpu.VMEM((R, 1), F32),
                pltpu.VMEM((R, 1), F32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={len(prefetch) + 2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_use_interpret(),
        name=f"latent_attention_h{height}",
    )(*prefetch, q, pool, out)


def latent_attend_tiles(pool, q, tiles: QueryTiles, dims: MLADims,
                        layer=None, heights: Tuple[int, int] = None):
    """The step's rows over their sequences' cached rows, by the kernel.

    pool: [rows, bs, >= row] (zeros behind a row's values; the stacked
    pool viewed ``[L * rows, ...]`` with ``layer = (base, rows)`` of the
    layer this call attends, ``None``: a pool of one layer); q: [T, H,
    row] folded queries (the stored type); ``tiles``: ``latent_tiles``
    of the step (cut at ``heights``, where a test gave it others)
    → [T, H, kv_rank] in q's type, zero in the rows of no tile."""
    T, H, _ = q.shape
    one, run = heights or tile_heights(H)
    base = 0 if layer is None else layer[0]
    # rows of 128 lanes as the pool's; and the element-offset window of
    # a tile that starts in the last rows reads past them
    qp = jnp.pad(q, ((0, run), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
    out = jnp.zeros((T, H, dims.kv_rank), q.dtype)
    for tl, height in ((tiles.long, run), (tiles.short, one)):
        out = _attend_tiles(tl, pool, qp, out, base, height, dims)
    return out


def _wide_kernel(tab_ref, row_ref, pos_ref, len_ref, base_ref, q_ref, w_ref,
                 pool_ref, o_ref, w_buf, buf_ref, key_ref, val_ref, acc_ref,
                 m_ref, l_ref, par_ref, sem_w, sem_in, *, block_size: int,
                 scale: float, kv_rank: int, nope: int, group: int,
                 rows: int):
    """A tile of the expanded form: the rows of one run inside a window
    of ``WIDE`` rows of the batch, at ``G`` heads (grid ``(tiles, H /
    G)``).  Per group of cached blocks and head: ``[k_n | v] = c
    W_kvb_h`` in VMEM, once for all the tile's rows, then the rows
    ``rows`` at a time (half of that at either end of the run, where it
    covers no more) under an online softmax."""
    t, hg = pl.program_id(0), pl.program_id(1)
    n_hg = pl.num_programs(1)
    step, steps = t * n_hg + hg, pl.num_programs(0) * n_hg
    G, per_head = w_buf.shape[0], w_buf.shape[-1]
    P = key_ref.shape[0]
    wide = q_ref.shape[1]
    keys = group * block_size
    half = rows // 2
    bs = jnp.int32(block_size)

    def last_block(tile):
        return jax.lax.div(
            pos_ref[tile] + jax.lax.max(len_ref[tile], jnp.int32(1)) - 1, bs)

    def fetch(do, step, g, slot):
        """``do`` the DMAs of group ``g`` of the tile of grid step
        ``step`` into buffer ``slot`` (``_tile_kernel``'s walk; every
        head group of a tile walks the tile's blocks again)."""
        tile = jax.lax.div(step, n_hg)

        def copies(i, block):
            return (pltpu.make_async_copy(
                pool_ref.at[block + base_ref[0]],
                buf_ref.at[slot, pl.ds(pl.multiple_of(i * block_size,
                                                      block_size),
                                       block_size)],
                sem_in.at[slot]),)

        each_group_block(do, tab_ref, tile, g * group, last_block(tile),
                         group, copies)

    def each_head_slice(do):
        """``do`` the DMA of every head's ``[kv_rank, nope + value]`` of
        ``W_kvb`` from where the matrix lies."""
        def one(h, _):
            at = pl.multiple_of((hg * G + h) * per_head, per_head)
            do(pltpu.make_async_copy(w_ref.at[:, pl.ds(at, per_head)],
                                     w_buf.at[h], sem_w.at[0]))

        jax.lax.fori_loop(0, G, one, None)

    @pl.when(step == 0)
    def _():
        buf_ref[...] = jnp.zeros_like(buf_ref)
        par_ref[0] = 0
        fetch(_start, 0, 0, 0)

    each_head_slice(_start)
    # the run's rows in the window, and the halves of a row-tile that
    # hold them; ``pos0``: the position of the window's row 0, were the
    # run to reach back to it
    lead = jax.lax.rem(row_ref[t], jnp.int32(wide))
    pos0, length = pos_ref[t] - lead, len_ref[t]
    h_lo = jax.lax.div(lead, jnp.int32(half))
    h_hi = jax.lax.div(lead + length + (half - 1), jnp.int32(half))
    r_lo, r_hi = jax.lax.div(h_lo + 1, 2), jax.lax.div(h_hi, 2)
    groups = jax.lax.div(last_block(t), jnp.int32(group)) + 1
    par = par_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    each_head_slice(_wait)

    def attend(g, _):
        slot = jax.lax.rem(par + g, 2)
        fetch_ahead(fetch, step, steps, g, groups, slot)
        # the shared rotated key (and the zeros behind it) as it lies
        for i in range(P):
            key_ref[i, :, nope:] = buf_ref[slot, :, kv_rank:]
        # window rows from which a row sees a key of this group
        seen_from = jax.lax.max(g * keys - pos0, 0)

        def heads(j, _):
            # ``P`` heads a trip: independent chains, so that one's
            # softmax lies under another's products
            for i in range(P):
                kv = jax.lax.dot_general(
                    buf_ref[slot, :, :kv_rank], w_buf[j * P + i],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=F32)            # [keys, n + v]
                key_ref[i, :, :nope] = kv[:, :nope].astype(key_ref.dtype)
                val_ref[i] = kv[:, nope:].astype(val_ref.dtype)

            def tile_rows(r, _, size):
                """The window's rows ``r * size`` and the ``size``
                behind it."""
                at = pl.ds(pl.multiple_of(r * size, size), size)
                qpos = pos0 + r * size + jax.lax.broadcasted_iota(
                    jnp.int32, (size, 1), 0)
                cols = g * keys + jax.lax.broadcasted_iota(
                    jnp.int32, (size, keys), 1)
                # this also masks whole the blocks of the group that lie
                # past the tile's last position and were not read, and
                # every key for a row before the run's first
                keep = cols <= qpos
                for i in range(P):
                    h = j * P + i
                    s = jax.lax.dot_general(
                        q_ref[h, at, :], key_ref[i],
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=F32) * scale  # [size, keys]
                    s = jax.lax.select(keep, s, jnp.full_like(s, NEG_INF))
                    m_prev = m_ref[h, at, :]
                    m_new = jnp.maximum(m_prev,
                                        s.max(axis=1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    corr = jnp.exp(m_prev - m_new)
                    m_ref[h, at, :] = m_new
                    l_ref[h, at, :] = l_ref[h, at, :] * corr \
                        + p.sum(axis=1, keepdims=True)
                    pv = jax.lax.dot_general(
                        p.astype(val_ref.dtype), val_ref[i],
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=F32)         # [size, value]
                    acc_ref[h, at, :] = acc_ref[h, at, :] * corr + pv

            # a half row-tile where the run starts in a row-tile's
            # second half, the whole ones, a half where it ends in a
            # first half (its last rows: they see every group)
            @pl.when((jax.lax.rem(h_lo, 2) == 1)
                     & (seen_from < (h_lo + 1) * half))
            def _():
                tile_rows(h_lo, None, half)

            jax.lax.fori_loop(
                jax.lax.max(r_lo, jax.lax.div(seen_from, jnp.int32(rows))),
                r_hi, functools.partial(tile_rows, size=rows), None)

            @pl.when(jax.lax.rem(h_hi, 2) == 1)
            def _():
                tile_rows(h_hi - 1, None, half)

        jax.lax.fori_loop(0, G // P, heads, None)
        return 0

    jax.lax.fori_loop(0, groups, attend, 0)
    par_ref[0] = jax.lax.rem(par + groups, 2)

    def finish(i, _):
        h = jax.lax.div(i, h_hi - h_lo)
        at = pl.ds(pl.multiple_of(
            (h_lo + jax.lax.rem(i, h_hi - h_lo)) * half, half), half)
        o_ref[h, at, :] = (acc_ref[h, at, :] / jnp.maximum(
            l_ref[h, at, :], 1e-30)).astype(o_ref.dtype)

    jax.lax.fori_loop(0, G * (h_hi - h_lo), finish, None)


def latent_attend_expanded(pool, q_n, q_r, w, tiles: TileList, o,
                           dims: MLADims, layer=None):
    """The runs of ``tiles`` (the step's third list: ``latent_tiles``
    with ``wide``) over their sequences' cached rows in the EXPANDED
    form, by the kernel: per-head keys ``[c W_kb_h | k_r]`` and values
    ``c W_vb_h`` built in VMEM a group of cached blocks at a time.

    pool, ``layer``: as ``latent_attend_tiles``'; q_n: [T, H, nope], q_r:
    [T, H, rope] rotated (the stored type); w: ``W_kvb`` as
    ``w_kvb`` gives it; o: [T, H, value_dim], the other rows' heads'
    values → o with the rows of ``tiles`` replaced."""
    T, H, _ = q_n.shape
    wide, rows = WIDE, WIDE_ROWS
    n = tiles.row.shape[0]
    bs, W = pool.shape[1:]
    G = wide_heads(dims)
    P = PAIR if G % PAIR == 0 else 1
    per_head = dims.nope_dim + dims.value_dim
    base = 0 if layer is None else layer[0]
    group = 1
    while 2 * group * bs <= min(WIDE_KEYS, tiles.tables.shape[1] * bs):
        group *= 2
    # the step's queries head-major in whole windows, as they meet
    # ``[k_n | k_r | the row's zeros]``: a tile reads its window
    windows = -(-T // wide)
    q = jnp.concatenate([q_n, q_r], axis=-1).transpose(1, 0, 2)
    q = jnp.pad(q, ((0, 0), (0, windows * wide - T),
                    (0, dims.nope_dim + W - dims.kv_rank - q.shape[-1])))
    prefetch = [tiles.tables, tiles.row, tiles.pos, tiles.length,
                jnp.reshape(base, (1,)).astype(jnp.int32)]
    out = pl.pallas_call(
        functools.partial(_wide_kernel, block_size=bs, scale=dims.scale,
                          kv_rank=dims.kv_rank, nope=dims.nope_dim,
                          group=group, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles.count, H // G),
            in_specs=[
                # (the primitive: ``//`` would bring the body of jnp's
                # floor division as whoever traced it first in this
                # process left it, with THAT call's stack in its
                # locations, into the kernel's serialised module and so
                # into the step program's key in the compile cache)
                pl.BlockSpec((G, wide, q.shape[-1]),
                             lambda t, hg, tables, row, *_:
                             (hg, jax.lax.div(row[t], jnp.int32(wide)), 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, G, wide, dims.value_dim),
                                   lambda t, hg, *_: (t, hg, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, dims.kv_rank, per_head), q.dtype),
                pltpu.VMEM((2, group * bs, W), pool.dtype),  # two groups
                pltpu.VMEM((P, group * bs, q.shape[-1]), pool.dtype),
                pltpu.VMEM((P, group * bs, dims.value_dim), pool.dtype),
                pltpu.VMEM((G, wide, dims.value_dim), F32),
                pltpu.VMEM((G, wide, 1), F32),
                pltpu.VMEM((G, wide, 1), F32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, H, wide, dims.value_dim),
                                       o.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_use_interpret(),
        name=f"latent_attention_h{wide}",
    )(*prefetch, q, w.reshape(dims.kv_rank, H * per_head).astype(q.dtype),
      pool)
    # a tile's rows lie in its window as in the batch
    i = jnp.arange(T)
    for k in range(n):
        held = ((i >= tiles.row[k]) & (i < tiles.row[k] + tiles.length[k])
                & (k < tiles.count))
        rows_k = jnp.tile(out[k].transpose(1, 0, 2), (windows, 1, 1))[:T]
        o = jnp.where(held[:, None, None], rows_k, o)
    return o


def latent_attend_runs(ap, pool, q_n, q_r, tiles: QueryTiles, dims: MLADims,
                       layer, dtype, heights: Tuple[int, int] = None):
    """A step's rows where its tiles have a third list, each run by the
    call its length takes → the heads' values [T, H, value_dim] in
    ``dtype``, zero in the rows of no tile.  The folded form's products
    (``fold_query``, the padded query, the zeroed result,
    ``unfold_output``: 0.5 ms a layer at 512 rows and 128 heads, all of
    it HBM traffic) are made for the rows that take that form, not for
    the step's:

    * the one-token runs' rows are gathered (a row a tile, at most one a
      slot), folded, attended by the one-token call, unfolded and
      scattered back;
    * the runs under ``expand_from`` rows take the folded run call over
      the step's rows as ``latent_attend_tiles`` makes it, under a
      ``cond`` on the list's count: a step whose chunk is long enough to
      expand has none, and a step of one-token rows has none either;
    * the third list's runs take ``latent_attend_expanded``.

    ap: the layer's parameters (``w_kvb``); pool, ``layer``, ``heights``:
    as ``latent_attend_tiles``'; q_n, q_r: as
    ``latent_attend_expanded``'."""
    T, H, _ = q_n.shape
    one, run = heights or tile_heights(H)
    base = 0 if layer is None else layer[0]
    width = pool.shape[-1]

    def attend(tl, q_n, q_r, height):
        """``tl``'s tiles over these rows by the folded call →
        [rows, H, value_dim]."""
        qf = jnp.pad(fold_query(ap, q_n, q_r, dims),
                     ((0, height), (0, 0), (0, width - dims.row)))
        out = jnp.zeros(q_n.shape[:2] + (dims.kv_rank,), q_n.dtype)
        return unfold_output(
            ap, _attend_tiles(tl, pool, qf, out, base, height, dims), dims,
            dtype)

    o = jax.lax.cond(
        tiles.long.count > 0, lambda: attend(tiles.long, q_n, q_r, run),
        lambda: jnp.zeros((T, H, dims.value_dim), dtype))
    short = tiles.short
    at = jnp.arange(short.row.shape[0], dtype=jnp.int32)
    mine = attend(short._replace(row=at), q_n[short.row], q_r[short.row],
                  one)
    o = o.at[jnp.where(at < short.count, short.row, T)].set(mine,
                                                             mode="drop")
    return latent_attend_expanded(pool, q_n, q_r, w_kvb(ap, dims),
                                  tiles.wide, o, dims, layer)


def _normed(x, scale, eps: float, times: float):
    """A latent's RMSNorm in float32, then its constant multiplier."""
    x = x.astype(F32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)
    return x if times == 1.0 else x * times


def w_kvb(ap, dims: MLADims):
    """``W_kvb`` as ``[kv_rank, H, nope + value]`` (a model with a query
    latent keeps it as the matrix ``[kv_rank, H * (nope + value)]``)."""
    return ap["w_kvb"].reshape(dims.kv_rank, dims.heads, -1)


def project(ap, h, cos, sin, positions, dims: MLADims, eps: float, mm):
    """A layer's projections of ``h [T, dm]`` → (q_n [T, H, nope], q_r
    [T, H, rope] rotated, the cache row ``[c | k_r]`` [T, row]: the
    latent after its norm, the shared key after its rotation).
    ``mm(x, w)``: the caller's matrix product."""
    if dims.q_rank:
        c_q = _normed(mm(h, ap["wq_a"]), ap["q_norm"], eps, dims.q_scale)
        q = mm(c_q.astype(h.dtype), ap["wq_b"]).reshape(
            h.shape[0], dims.heads, -1)
    else:
        q = mm(h, ap["wq"])                             # [T, H, nope + rope]
    q_n, q_r = q[..., :dims.nope_dim], q[..., dims.nope_dim:]
    kva = mm(h, ap["w_kva"])                                 # [T, row]
    c = _normed(kva[..., :dims.kv_rank], ap["c_norm"], eps, dims.kv_scale)
    k_r = rope_interleaved(kva[..., dims.kv_rank:], cos, sin, positions)
    q_r = rope_interleaved(q_r, cos, sin, positions)
    return q_n, q_r, jnp.concatenate([c.astype(h.dtype), k_r], axis=-1)


def fold_query(ap, q_n, q_r, dims: MLADims):
    """``[q_n W_kb^T | q_r]``: the query as it meets a cache row."""
    w_kb = w_kvb(ap, dims)[..., :dims.nope_dim].astype(q_n.dtype)  # [c,H,n]
    return jnp.concatenate(
        [jnp.einsum("thn,chn->thc", q_n, w_kb), q_r], axis=-1)


def unfold_output(ap, o, dims: MLADims, dtype):
    """``o [T, H, kv_rank]`` over the latents → the heads' values."""
    w_vb = w_kvb(ap, dims)[..., dims.nope_dim:].astype(dtype)   # [c, H, v]
    return jnp.einsum("thc,chv->thv", o.astype(dtype), w_vb)


def attention_forward(ap, h, cos, sin, dims: MLADims, eps: float):
    """The layer's attention over whole sequences, keys and values
    expanded for every token.  h: [B, S, dm] → [B, S, H, value_dim]."""
    B, S, _ = h.shape
    dt = h.dtype
    pos = jnp.tile(jnp.arange(S), B)

    def mm(x, w):
        return jnp.tensordot(x, w.astype(dt), 1)

    q_n, q_r, row = project(ap, h.reshape(B * S, -1), cos, sin, pos, dims,
                            eps, mm)
    c, k_r = row[..., :dims.kv_rank], row[..., dims.kv_rank:]
    kv = jnp.einsum("tc,chx->thx", c, w_kvb(ap, dims).astype(dt))
    k_n, v = kv[..., :dims.nope_dim], kv[..., dims.nope_dim:]

    def seqs(t):
        return t.reshape((B, S) + t.shape[1:])

    s = (jnp.einsum("bqhn,bkhn->bhqk", seqs(q_n), seqs(k_n))
         + jnp.einsum("bqhr,bkr->bhqk", seqs(q_r), seqs(k_r))
         ).astype(F32) * dims.scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhv->bqhv", p, seqs(v))
