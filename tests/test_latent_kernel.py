"""The latent-attention Pallas kernel (``ops/mla.py``
``latent_attend_tiles``), interpreted on the CPU at tiny shapes: against
the XLA formulation ``latent_attend`` and against a dense float32 softmax
over keys and values expanded for every token."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mla as A
from deepspeed_tpu.ops.paged_attention import tile_counts

BS = 8              # rows a block
NB = 6              # blocks a sequence's table holds: groups of 4
BLOCKS = 40         # blocks a layer (the trash block behind them)
HEIGHTS = (1, 4)    # small tiles: a run of 11 rows is three of them

# (slot, cached rows before the step, rows this step); slot 1 has no row
RUNS = [(0, 37, 1),     # a one-token run deep in its sequence
        (2, 15, 1),     # whose context ends on a block boundary (16 rows)
        (3, 0, 1),      # a context of a single row
        (4, 5, 11),     # crosses two block boundaries, ends inside a tile
        (5, 0, 6),      # a second run in the step, from position 0
        (6, 9, 1)]
SLOTS = 8
PAD = 3             # rows that pad the step


def _dims(heads, kv_rank, rope):
    return A.MLADims(heads=heads, kv_rank=kv_rank, nope_dim=12,
                     rope_dim=rope, value_dim=10)


def _step(dims, seed=0, layer=1, poison=False):
    """A step's flat rows over a pool of two layers: (pool, layer,
    seq_slot, positions, valid, tables [SLOTS, NB], folded q, q_n, q_r,
    ap).  Slot 2 shares slot 0's first two blocks (an aliased prefix)."""
    rng = np.random.default_rng(seed)
    width = -(-dims.row // 128) * 128
    rows = BLOCKS + 1
    pool = np.zeros((2 * rows, BS, width), np.float32)
    pool[..., :dims.row] = rng.standard_normal((2 * rows, BS, dims.row))
    tables = np.full((SLOTS, NB), -1, np.int32)
    free = list(rng.permutation(BLOCKS))
    last = {}
    for slot, seen, n in RUNS:
        need = -(-(seen + n) // BS)
        tables[slot, :need] = [free.pop() for _ in range(need)]
        last[slot] = need
    tables[2, :2] = tables[0, :2]
    if poison:
        # every block behind a sequence's last position, and the trash
        bad = [free.pop() for _ in range(3)]
        pool[layer * rows + np.asarray(bad)] = np.nan
        for slot, need in last.items():
            for b in range(need, NB):
                tables[slot, b] = bad[b % 3]
        pool[layer * rows + rows - 1] = np.nan
    slot_of, pos_of = [], []
    for slot, seen, n in RUNS:
        slot_of += [slot] * n
        pos_of += list(range(seen, seen + n))
    T = len(slot_of) + PAD
    valid = np.arange(T) < len(slot_of)
    slot_of += [0] * PAD
    pos_of += [0] * PAD
    q_n = rng.standard_normal((T, dims.heads, dims.nope_dim)).astype(
        np.float32)
    q_r = rng.standard_normal((T, dims.heads, dims.rope_dim)).astype(
        np.float32)
    ap = {"w_kvb": jnp.asarray(rng.standard_normal(
        (dims.kv_rank, dims.heads * (dims.nope_dim + dims.value_dim))
    ).astype(np.float32) * dims.kv_rank ** -0.5)}
    qf = A.fold_query(ap, jnp.asarray(q_n), jnp.asarray(q_r), dims)
    return dict(pool=jnp.asarray(pool), layer=(layer * rows, rows),
                slot=jnp.asarray(slot_of, jnp.int32),
                pos=jnp.asarray(pos_of, jnp.int32),
                valid=jnp.asarray(valid), tables=jnp.asarray(tables),
                qf=qf, q_n=q_n, q_r=q_r, ap=ap, T=T)


def _kernel(dims, st, heights=HEIGHTS):
    tiles = A.latent_tiles(
        st["slot"], st["pos"], st["valid"], st["tables"], BS, NB,
        trash=st["layer"][1] - 1, heads=dims.heads, heights=heights)
    return tiles, np.asarray(A.latent_attend_tiles(
        st["pool"], st["qf"], tiles, dims, st["layer"], heights))


def _layer_rows(st):
    base, rows = st["layer"]
    t = st["tables"][st["slot"]]
    return jnp.where(t < 0, rows - 1, t) + base                # [T, NB]


def _xla(dims, st):
    """``latent_attend`` with a group a row."""
    qpos = jnp.where(st["valid"], st["pos"], -1)
    return np.asarray(A.latent_attend(
        st["pool"], st["qf"][:, None], qpos[:, None], _layer_rows(st),
        dims, blocks=2)[:, 0])


def _dense(dims, st):
    """Keys and values expanded for every cached row, a full masked
    score matrix a token, float32 → the heads' values [T, H, V]."""
    ctx = np.asarray(st["pool"])[np.asarray(_layer_rows(st))].reshape(
        st["T"], NB * BS, -1)
    c, k_r = ctx[..., :dims.kv_rank], ctx[..., dims.kv_rank:dims.row]
    w = np.asarray(A.w_kvb(st["ap"], dims))
    kv = np.einsum("tjc,chx->tjhx", c, w)
    k_n, v = kv[..., :dims.nope_dim], kv[..., dims.nope_dim:]
    s = (np.einsum("thn,tjhn->thj", st["q_n"], k_n)
         + np.einsum("thr,tjr->thj", st["q_r"], k_r)) * dims.scale
    keep = np.arange(NB * BS)[None, :] <= np.asarray(st["pos"])[:, None]
    s = np.where(keep[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("thj,tjhv->thv", p, v)


# the 64- and the 32-head geometry scaled down: rows of 128 and 256 lanes
GEOMETRIES = {"4h-128": (4, 96, 16), "2h-256": (2, 192, 32)}


@pytest.fixture(scope="module", params=sorted(GEOMETRIES))
def case(request):
    dims = _dims(*GEOMETRIES[request.param])
    st = _step(dims, seed=len(request.param))
    tiles, out = _kernel(dims, st)
    return dims, st, tiles, out


def test_tiles_follow_the_runs(case):
    dims, st, tiles, _ = case
    assert int(tiles.short.count) == 4          # the one-token runs
    n = int(tiles.long.count)
    assert n == 3 + 2                           # 11 rows and 6 in fours
    assert sorted(np.asarray(tiles.long.length)[:n]) == [2, 3, 4, 4, 4]
    # the deepest one-token tile reads 5 blocks (two groups of 4: the
    # table holds 6), the deepest run tile 2
    deepest = [int(np.max(np.asarray(tl.pos + tl.length - 1)[:int(tl.count)]))
               // BS + 1 for tl in (tiles.short, tiles.long)]
    assert deepest == [5, 2]
    assert A.latent_group(dims.heads, st["pool"].shape[-1], BS,
                          jnp.float32, NB) == 4


def test_kernel_matches_the_xla_formulation(case):
    dims, st, _, out = case
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(out[valid], _xla(dims, st)[valid],
                               rtol=2e-5, atol=2e-5)


def test_kernel_matches_dense_softmax_over_expanded_rows(case):
    dims, st, _, out = case
    valid = np.asarray(st["valid"])
    got = np.asarray(A.unfold_output(st["ap"], jnp.asarray(out), dims,
                                     jnp.float32))
    np.testing.assert_allclose(got[valid], _dense(dims, st)[valid],
                               rtol=1e-4, atol=1e-4)


def test_rows_that_pad_the_step_are_left_zero(case):
    _, st, _, out = case
    assert not np.asarray(st["valid"])[-PAD:].any()
    assert (out[-PAD:] == 0).all()


def test_aliased_prefix_reads_the_shared_blocks(case):
    dims, st, _, out = case
    tables = np.asarray(st["tables"])
    assert (tables[2, :2] == tables[0, :2]).all()
    # slot 2's one row sees exactly the shared 16 rows
    row = 1
    assert int(st["slot"][row]) == 2 and int(st["pos"][row]) == 15
    np.testing.assert_allclose(out[row], _xla(dims, st)[row],
                               rtol=2e-5, atol=2e-5)


def test_first_layer_of_the_stack_and_a_pool_of_one_layer():
    """Layer 0 of the stack, and the same rows as a pool of their own
    (``layer=None``), give one answer (the cases above read layer 1, at
    a base that is not 0)."""
    dims = _dims(*GEOMETRIES["4h-128"])
    st = _step(dims, seed=3, layer=0)
    tiles, stacked = _kernel(dims, st)
    rows = st["layer"][1]
    np.testing.assert_array_equal(stacked, np.asarray(A.latent_attend_tiles(
        st["pool"][:rows], st["qf"], tiles, dims, None, HEIGHTS)))
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(stacked[valid], _xla(dims, st)[valid],
                               rtol=2e-5, atol=2e-5)


def test_blocks_behind_a_tile_are_not_read():
    """NaN in every block behind a sequence's last position and in the
    trash block: a group behind a tile's last position is skipped, a
    block of the last group that lies behind it is not fetched."""
    dims = _dims(*GEOMETRIES["4h-128"])
    st = _step(dims, seed=5, poison=True)
    _, out = _kernel(dims, st)
    assert np.isfinite(out).all()
    clean = _step(dims, seed=5)
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(out[valid], _xla(dims, clean)[valid],
                               rtol=2e-5, atol=2e-5)


def test_the_kernels_own_heights_and_group():
    """``tile_heights`` and ``latent_group`` at the two served
    geometries, and the kernel at its own heights (a tile holds a whole
    run here) against the small tiles'."""
    assert A.tile_heights(64) == (1, 16) and A.tile_heights(32) == (1, 32)
    assert A.tile_heights(4) == (1, 128)
    for heads in (64, 32):
        one, run = A.tile_heights(heads)
        assert [A.latent_group(height * heads, 640, 64, jnp.bfloat16, 160)
                for height in (one, run)] == [16, 8]
    assert A.latent_group(64, 640, 64, jnp.bfloat16, 2) == 2
    dims = _dims(*GEOMETRIES["2h-256"])
    st = _step(dims, seed=7)
    _, small = _kernel(dims, st)
    tiles, own = _kernel(dims, st, heights=None)
    assert int(tiles.long.count) == 2
    np.testing.assert_allclose(own, small, rtol=2e-5, atol=2e-5)


def test_bf16_rows_and_float32_statistics():
    """The stored type: bf16 rows and products, float32 scores and
    accumulator, as ``latent_attend``'s."""
    dims = _dims(*GEOMETRIES["4h-128"])
    st = _step(dims, seed=9)
    st = dict(st, pool=st["pool"].astype(jnp.bfloat16),
              qf=st["qf"].astype(jnp.bfloat16))
    _, out = _kernel(dims, st)
    assert out.dtype == jnp.bfloat16
    valid = np.asarray(st["valid"])
    ref = _xla(dims, st).astype(np.float32)
    np.testing.assert_allclose(out.astype(np.float32)[valid], ref[valid],
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("lengths,heights,want", [
    ([1, 1, 464, 1], (1, 16), (3, 29, 464)),
    ([1, 33, 0, 2], (1, 32), (1, 3, 35)),
    ([8, 9], (8, 128), (1, 1, 9))])
def test_tile_counts_at_other_heights(lengths, heights, want):
    assert tile_counts(lengths, *heights) == want
