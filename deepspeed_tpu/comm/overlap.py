"""T3-style decomposed collectives + EQuARX-style quantized allreduce.

The multi-chip hot paths (TP serving, ZeRO gradient sync) spend their
collectives *serially* with compute: GSPMD inserts one monolithic
all-reduce after each row-parallel GEMM and one reduce-scatter per
gradient leaf, and nothing else can run while it drains.  T3
(arxiv 2401.16677) hides that wire time by decomposing each collective
into tiles whose communication carries no data dependency on the next
tile's GEMM — XLA's scheduler is then free to run tile *i*'s reduction
behind tile *i+1*'s matmul.  EQuARX (arxiv 2506.17615) stacks a second
win on top: quantizing the all-reduce payload inside the program is a
near-free 2x (int8) / 4x (int4) on the wire.

Everything here is written to run **inside shard_map** (manual mesh
axes); the ``shard_*`` entry points at the bottom wrap the tiled bodies
in a full-manual ``shard_map`` for use from GSPMD-sharded jit programs
(the serving forward).  Every comm stage carries a ``jax.named_scope``
label so ``tools/tracemerge.py`` renders the tile chain as distinct
device slices next to the GEMMs they overlap (the measurement bar for
this whole module).

The exactness ladder (docs/SERVING.md "Overlapped & quantized
collectives"):

* ``strategy="psum"`` (default) — per-tile ``lax.psum`` /
  ``psum_scatter``.  Collective reduction is elementwise, and splitting
  rows into tiles does not change any element's cross-rank reduction
  order, so the result is **bitwise-identical** to the serial baseline
  (asserted by tests on 1-chip and 8-device meshes).
* ``strategy="ring"`` — explicit ppermute ring (reduce-scatter +
  all-gather hops).  Exact arithmetic over the same summands, but the
  per-destination accumulation order is a ring rotation, so results can
  differ from ``psum`` in the last ulp.  Maximum scheduling freedom —
  each 1/n-sized hop is its own schedulable op.
* ``quant_bits=8|4`` — quantized wire (grouped int8/int4 payloads,
  ``ops/quant.py``).  Error-bounded, not exact; the bound is asserted
  in tests and documented.  Gather-only collectives (the unembed's
  logits all-gather) never quantize — pure data movement stays bitwise.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P


STRATEGIES = ("psum", "ring")


def _resolve_tiles(rows: int, tiles: int) -> int:
    """Largest tile count <= ``tiles`` that divides ``rows``."""
    t = max(1, min(int(tiles), int(rows) or 1))
    while rows % t:
        t -= 1
    return t


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


# --------------------------------------------------------------------------
# ring primitives (ppermute chains)
# --------------------------------------------------------------------------

def ring_all_gather(x, axis_name: str, axis: int = 0,
                    scope: str = "ring_ag"):
    """All-gather along ``axis`` as an n-1 hop ppermute chain.

    Pure data movement — bitwise-identical to
    ``lax.all_gather(..., tiled=True)`` — but each hop is its own
    schedulable op, so XLA can interleave the chain with unrelated
    compute.  After ``s`` rotations rank ``r`` holds rank ``r-s``'s
    shard; the stack is rolled into absolute-rank order before the
    concat so every rank assembles the same layout."""
    n = axis_size(axis_name)
    if n == 1:
        return x
    parts = [x]
    cur = x
    perm = _ring_perm(n)
    for s in range(n - 1):
        with jax.named_scope(f"{scope}_hop{s}"):
            cur = jax.lax.ppermute(cur, axis_name, perm)
        parts.append(cur)
    st = jnp.stack(parts)                      # slot s <- rank (r - s)
    r = jax.lax.axis_index(axis_name)
    st = st[(r - jnp.arange(n)) % n]           # absolute-rank order
    return jnp.moveaxis(st, 0, axis).reshape(
        x.shape[:axis] + (n * x.shape[axis],) + x.shape[axis + 1:])


def ring_reduce_scatter(x, axis_name: str, scatter_dim: int = 0,
                        scope: str = "ring_rs"):
    """Classic ring reduce-scatter: the partial destined for each rank
    travels the ring accumulating every rank's chunk — n-1 hops of
    1/n-sized payload (the bandwidth-optimal wire pattern).  EXACT
    arithmetic over the same summands as ``psum_scatter``, but the
    accumulation order is a ring rotation, so the result need not be
    bit-identical to it (exactness ladder, docs/SERVING.md)."""
    n = axis_size(axis_name)
    if n == 1:
        return x
    if scatter_dim != 0:
        x = jnp.moveaxis(x, scatter_dim, 0)
    D = x.shape[0]
    assert D % n == 0, (x.shape, n)
    chunks = x.reshape(n, D // n, *x.shape[1:])
    r = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    # the partial for destination d starts at rank d+1 and accumulates
    # chunks_j[d] at every rank j it visits, landing home after n-1 hops
    acc = chunks[(r - 1) % n]
    for s in range(1, n):
        with jax.named_scope(f"{scope}_hop{s - 1}"):
            acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + chunks[(r - 1 - s) % n]
    if scatter_dim != 0:
        acc = jnp.moveaxis(acc, 0, scatter_dim)
    return acc


def ring_all_reduce(x, axis_name: str, scope: str = "ring_ar"):
    """Ring allreduce = ring reduce-scatter + ring all-gather over the
    flattened (zero-padded to a multiple of n) payload — 2(n-1)/n of
    the data on the wire, every hop independently schedulable."""
    n = axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    red = ring_reduce_scatter(flat, axis_name, scope=scope)
    out = ring_all_gather(red, axis_name, scope=scope)
    if pad:
        out = out[:x.size]
    return out.reshape(x.shape)


# --------------------------------------------------------------------------
# tiled (overlappable) collectives
# --------------------------------------------------------------------------

def _tile_all_reduce(p, axis_name: str, strategy: str,
                     quant_bits: Optional[int], scope: str):
    """One tile's partial-sum reduction on the chosen rung of the
    exactness ladder."""
    if quant_bits:
        from ..ops.quant import quantized_all_reduce
        with jax.named_scope(f"{scope}_qar{quant_bits}"):
            return quantized_all_reduce(p, axis_name, bits=quant_bits,
                                        pad=True)
    if strategy == "ring":
        return ring_all_reduce(p, axis_name, scope=scope)
    with jax.named_scope(f"{scope}_ar"):
        return jax.lax.psum(p, axis_name)


def overlapped_matmul_allreduce(x, w, axis_name: str, tiles: int = 4,
                                strategy: str = "psum",
                                quant_bits: Optional[int] = None,
                                out_dtype=None,
                                scope: str = "t3_mm_ar"):
    """Row-parallel matmul + allreduce, tile-decomposed T3-style.

    Call INSIDE shard_map.  ``x``: [rows, K_local]; ``w``: [K_local, N]
    — this rank's contraction shard.  The row dim splits into ``tiles``
    tiles; tile *i*'s partial-sum reduction carries no dependency on
    tile *i+1*'s GEMM, so XLA may co-schedule them (the named scopes
    make the interleaving visible in a merged tracemerge timeline).

    ``strategy="psum"`` is bitwise-identical to the serial
    ``psum(x @ w)`` for any tile count; see the module docstring's
    exactness ladder for "ring" and ``quant_bits``."""
    assert strategy in STRATEGIES, strategy
    dt = out_dtype or x.dtype
    rows = x.shape[0]
    t = _resolve_tiles(rows, tiles)
    step = rows // t
    outs = []
    for i in range(t):
        with jax.named_scope(f"{scope}_gemm_t{i}"):
            p = (x[i * step:(i + 1) * step] @ w.astype(dt)).astype(dt)
        outs.append(_tile_all_reduce(p, axis_name, strategy, quant_bits,
                                     f"{scope}_comm_t{i}"))
    return outs[0] if t == 1 else jnp.concatenate(outs, axis=0)


def overlapped_matmul_allgather(x, w, axis_name: str, tiles: int = 4,
                                out_dtype=None,
                                scope: str = "t3_mm_ag"):
    """Column-parallel matmul + all-gather (the unembed shape),
    tile-decomposed.

    Call INSIDE shard_map.  ``x``: [rows, K] (replicated contraction);
    ``w``: [K, N_local].  Tile *i*'s ppermute gather chain overlaps tile
    *i+1*'s GEMM.  The gather is pure data movement, so the result is
    bitwise-identical to the serial GSPMD matmul + all-gather for any
    tile count — which is why the logits gather never quantizes (a
    perturbed logit could flip a greedy argmax)."""
    dt = out_dtype or x.dtype
    rows = x.shape[0]
    t = _resolve_tiles(rows, tiles)
    step = rows // t
    outs = []
    for i in range(t):
        with jax.named_scope(f"{scope}_gemm_t{i}"):
            p = (x[i * step:(i + 1) * step] @ w.astype(dt)).astype(dt)
        outs.append(ring_all_gather(p, axis_name, axis=1,
                                    scope=f"{scope}_comm_t{i}"))
    return outs[0] if t == 1 else jnp.concatenate(outs, axis=0)


def overlapped_all_reduce(x, axis_name: str, tiles: int = 4,
                          strategy: str = "psum",
                          quant_bits: Optional[int] = None,
                          scope: str = "t3_ar"):
    """Tiled allreduce for replicated leaves (ZeRO grad sync of leaves
    no mesh axis owns).  Tiles along dim 0 when it divides; scalars and
    indivisible leaves run as one tile."""
    assert strategy in STRATEGIES, strategy
    if x.ndim == 0:
        # a scalar has no quantization group or ring chunk; the exact
        # psum stands in on every rung of the ladder
        with jax.named_scope(f"{scope}_ar"):
            return jax.lax.psum(x, axis_name)
    t = _resolve_tiles(x.shape[0], tiles)
    step = x.shape[0] // t
    outs = [_tile_all_reduce(x[i * step:(i + 1) * step], axis_name,
                             strategy, quant_bits, f"{scope}_t{i}")
            for i in range(t)]
    return outs[0] if t == 1 else jnp.concatenate(outs, axis=0)


def _rs_tile_dim(shape, scatter_dim: int, tiles: int) -> Optional[int]:
    """Largest dim other than ``scatter_dim`` that ``tiles`` divides —
    tiling along the scattered dim itself would permute the output
    layout relative to the serial ``psum_scatter``."""
    best = None
    for d, s in enumerate(shape):
        if d == scatter_dim or tiles <= 1 or s % tiles or s < tiles:
            continue
        if best is None or s > shape[best]:
            best = d
    return best


def overlapped_reduce_scatter(x, axis_name: str, scatter_dim: int = 0,
                              tiles: int = 4, strategy: str = "psum",
                              quant_bits: Optional[int] = None,
                              scope: str = "t3_rs"):
    """Tiled reduce-scatter for ZeRO stage-2/3 gradient sync.

    Call INSIDE shard_map.  The leaf is split into ``tiles`` slices
    along its largest non-scattered dim (a leaf with no such dim runs
    serial), each slice reduced by ``psum_scatter`` (bitwise vs the
    serial op), a ppermute ring, or the qgZ int8/int4 wire — so the
    reduce-scatter of gradient slice *i* can ride behind whatever
    compute (the next microbatch's backward GEMMs) XLA has in flight."""
    assert strategy in STRATEGIES, strategy
    n = axis_size(axis_name)

    def one(xt, sc):
        if quant_bits:
            from ..ops.quant import quantized_psum_scatter_dim
            with jax.named_scope(f"{sc}_qrs{quant_bits}"):
                return quantized_psum_scatter_dim(xt, axis_name,
                                                  dim=scatter_dim,
                                                  bits=quant_bits)
        if strategy == "ring" and xt.shape[scatter_dim] % n == 0:
            return ring_reduce_scatter(xt, axis_name,
                                       scatter_dim=scatter_dim, scope=sc)
        with jax.named_scope(f"{sc}_rs"):
            return jax.lax.psum_scatter(xt, axis_name,
                                        scatter_dimension=scatter_dim,
                                        tiled=True)

    td = _rs_tile_dim(x.shape, scatter_dim, tiles)
    if td is None:
        return one(x, f"{scope}_t0")
    t = _resolve_tiles(x.shape[td], tiles)
    step = x.shape[td] // t
    idx = [slice(None)] * x.ndim
    outs = []
    for i in range(t):
        idx[td] = slice(i * step, (i + 1) * step)
        outs.append(one(x[tuple(idx)], f"{scope}_t{i}"))
    return outs[0] if t == 1 else jnp.concatenate(outs, axis=td)


# --------------------------------------------------------------------------
# wire accounting
# --------------------------------------------------------------------------

def wire_bytes(op: str, elems: int, itemsize: float, n: int,
               quant_bits: Optional[int] = None) -> float:
    """Modeled per-rank bytes on the wire for one collective over ``n``
    ranks, NCCL-style (the ``comms_logging.calc_bw_log`` factors):
    all-reduce moves 2(n-1)/n of the payload, reduce-scatter /
    all-gather (n-1)/n, everything else the payload.  A quantized op's
    payload is ``bits/8`` bytes per element instead of ``itemsize`` —
    exactly the bits/8 ratio the telemetry reconciliation test asserts
    (scale sidecars are excluded from both sides of the ratio by
    design; they are <1% of payload at the default group size)."""
    if n <= 1:
        return 0.0
    payload = elems * ((quant_bits / 8.0) if quant_bits else itemsize)
    if op == "all_reduce":
        return payload * 2 * (n - 1) / n
    if op in ("reduce_scatter", "all_gather"):
        return payload * (n - 1) / n
    return payload


# --------------------------------------------------------------------------
# GSPMD-context entry points (the serving forward)
# --------------------------------------------------------------------------

class ServingComm(NamedTuple):
    """Resolved serving-side comm plan, built once by
    ``InferenceEngine._resolve_serving_comm`` and threaded through the
    compiled forward: which of the two heavy TP collectives run
    decomposed, over which mesh/axis, at what tile count, and whether
    the all-reduce payload rides the quantized wire."""
    mesh: object                 # jax.sharding.Mesh
    axis_name: str               # the tensor-parallel mesh axis
    tiles: int
    quant_bits: Optional[int]    # None = exact; 8 | 4 = EQuARX wire
    downproj: bool               # MLP down-projection all-reduce
    unembed: bool                # logits all-gather


def shard_matmul_allreduce(x, w, comm: ServingComm, dt):
    """Tile-decomposed row-parallel matmul+allreduce, callable from a
    GSPMD-sharded jit program: wraps the tiled body in a full-manual
    shard_map over ``comm.mesh``.  ``x``: [..., K] with K sharded over
    ``comm.axis_name``; ``w``: [K, N] sharded on dim 0.  Returns the
    replicated [..., N] product in ``dt``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    f = shard_map(
        lambda a, b: overlapped_matmul_allreduce(
            a, b, comm.axis_name, tiles=comm.tiles,
            quant_bits=comm.quant_bits, out_dtype=dt),
        mesh=comm.mesh,
        in_specs=(P(None, comm.axis_name), P(comm.axis_name, None)),
        out_specs=P(), check_vma=False)
    return f(x2, w).reshape(*lead, w.shape[-1])


def shard_matmul_allgather(x, w, comm: ServingComm, dt):
    """Tile-decomposed column-parallel matmul+all-gather (the unembed),
    callable from a GSPMD-sharded jit program.  ``x``: [..., K]
    replicated; ``w``: [K, N] with N sharded over ``comm.axis_name``.
    Returns the replicated [..., N] logits in ``dt`` — bitwise-equal to
    the serial path (the gather moves data, it never rounds)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    f = shard_map(
        lambda a, b: overlapped_matmul_allgather(
            a, b, comm.axis_name, tiles=comm.tiles, out_dtype=dt),
        mesh=comm.mesh,
        in_specs=(P(), P(None, comm.axis_name)),
        out_specs=P(), check_vma=False)
    return f(x2, w).reshape(*lead, w.shape[-1])
