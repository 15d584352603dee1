"""Grouped matrix multiplication — Pallas TPU kernel for sparse experts.

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, the rows
of a group lying next to each other in ``lhs`` (tokens sorted by the
expert they were routed to) and ``group_sizes`` saying how many rows
each group has.  The megablox formulation, and the TPU analog of the
reference's cutlass ``moe_gemm`` (inference/v2/kernels/cutlass_ops).

Written for serving, where a step holds a few rows for each of many
experts and the time is the read of the experts' weights:

* the grid is ``(n tiles, visits)``; a *visit* is one (row tile, group)
  pair that share rows, in row order, so a group's weights stream
  through VMEM once per n tile whatever the batch holds, and groups
  without rows are never visited (their weights are not read);
* the visit list (group, row tile, group starts, number of real visits)
  rides scalar prefetch, so the block index maps pick the weight block
  and the row tile of each visit dynamically: the indirection happens
  in the DMA engine;
* the contraction is not tiled: a ``[tm, K] x [K, tn]`` product per
  visit, masked to the group's rows and merged into the output tile
  that stays in VMEM while consecutive visits share it;
* the grid is static (``row tiles + groups - 1`` visits bound every
  case); visits past the real ones repeat the last one's blocks, which
  costs no DMA, and skip the product.

Rows past the last group (``sum(group_sizes) < m``) come back zero.

XLA's own lowering of ``jax.lax.ragged_dot`` on this chip is a kernel of
the same family with 512-row tiles, so each of the 64 groups of a decode
step pays a 512-row product for its dozen rows; see PERF.md, PR 26.
CPU tests run this kernel in interpret mode; the serving path off the
TPU uses ``jax.lax.ragged_dot`` (``parallel/moe.py`` ``moe_serve``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128                  # rows a visit multiplies (see PERF.md)
WEIGHT_BLOCK_BYTES = 2 << 20    # a [K, tn] weight block; two are in flight


def visit_metadata(group_sizes, m: int, tm: int):
    """The visit list of ``m`` rows in tiles of ``tm``: ``(group ids
    [V], row-tile ids [V], group starts [G + 1], real visits [1])`` with
    ``V = m // tm + G - 1``.  Visit ``v`` multiplies the rows that tile
    ``tile[v]`` and group ``group[v]`` share; visits come in row order,
    so visits of one tile are consecutive.  Visits past the real ones
    repeat the last real one."""
    G = group_sizes.shape[0]
    tiles = m // tm
    V = tiles + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visits_end = jnp.cumsum(visits)
    n_real = visits_end[-1]
    v = jnp.clip(jnp.arange(V, dtype=jnp.int32), 0,
                 jnp.maximum(n_real - 1, 0))
    group = jnp.clip(jnp.searchsorted(visits_end, v, side="right"),
                     0, G - 1).astype(jnp.int32)
    tile = first_tile[group] + v - (visits_end[group] - visits[group])
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    bounds = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return group, tile, bounds, n_real.reshape(1).astype(jnp.int32)


def _kernel(group_ref, tile_ref, bounds_ref, n_real_ref, first_ref,
            lhs_ref, rhs_ref, out_ref, *, tm: int):
    del first_ref       # the weight block's index map's alone
    v = pl.program_id(1)
    g = group_ref[v]
    t = tile_ref[v]

    # the first visit of a row tile owns its rows no group will write
    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(v < n_real_ref[0])
    def _visit():
        rows = t * tm + jax.lax.broadcasted_iota(
            jnp.int32, out_ref.shape, 0)
        mine = (rows >= bounds_ref[g]) & (rows < bounds_ref[g + 1])
        acc = jnp.dot(lhs_ref[...], rhs_ref[0],
                      preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def _n_tile(k: int, n: int, itemsize: int) -> int:
    tn = n
    while k * tn * itemsize > WEIGHT_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def grouped_matmul(lhs, rhs, group_sizes, *, first_group=0,
                   tm: int = ROW_TILE):
    """lhs: [m, K]; rhs: [>= first_group + G, K, N]; group_sizes: [G]
    i32, the rows of group ``g`` being ``lhs[starts[g]:starts[g] +
    group_sizes[g]]`` and its weights ``rhs[first_group + g]`` → [m, N]
    in ``lhs.dtype``, float32 accumulation.

    ``first_group`` (a traced scalar will do) is how a layer scan hands
    over the experts of ALL layers, viewed ``[L * E, K, N]``, and says
    ``li * E``: the index map picks the block where it lies.  A layer's
    weights sliced out of the stack first are a copy of them all, which
    XLA cannot fuse into a custom call (2.5 ms a layer at olmoe-1b-7b's
    sizes, a third of the step: PERF.md, PR 26).
    Off the TPU the kernel runs in interpret mode (the CPU tests)."""
    m, K = lhs.shape
    G, N = group_sizes.shape[0], rhs.shape[2]
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    mp = m + pad
    tn = _n_tile(K, N, rhs.dtype.itemsize)
    group, tile, bounds, n_real = visit_metadata(group_sizes, mp, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, mp // tm + G - 1),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, g, t, *_: (t[v], 0)),
                pl.BlockSpec((1, K, tn),
                             lambda n, v, g, t, b, r, first:
                             (first[0] + g[v], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, g, t, *_: (t[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="moe_grouped_matmul",
    )(group, tile, bounds, n_real,
      jnp.asarray(first_group, jnp.int32).reshape(1), lhs, rhs)
    # row tiles no group reaches were never visited: nothing wrote them
    live = jnp.arange(mp)[:, None] < bounds[-1]
    return jnp.where(live, out, 0)[:m]
