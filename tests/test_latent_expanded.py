"""The latent kernel's third call, the EXPANDED form (``ops/mla.py``
``latent_attend_expanded``: per-head keys and values built in VMEM, for
the runs of at least ``expand_from(dims)`` rows), interpreted on the CPU
at tiny shapes: against the XLA formulation ``latent_attend`` and against
a dense float32 softmax over keys and values expanded for every token;
which run takes which call; the host's counts of it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mla as A
from deepspeed_tpu.ops.paged_attention import tile_counts

BS = 8              # rows a block
NB = 8              # blocks a sequence's table holds: groups of 8
BLOCKS = 48         # blocks a layer (the trash block behind them)
HEIGHTS = (1, 4)    # the folded calls' tiles
WIDE, ROWS = 32, 8  # the expanded call's: a run of 37 rows is two tiles
SLOTS = 8
T = 72              # rows of a step, three or more of them padding
PAD = 3

# (slot, cached rows before the step, rows this step); at these sizes a
# run of 16 rows or more expands
STEPS = {
    # two long runs beside one-token runs and a short run
    "mixed": [(0, 37, 1), (2, 15, 1), (4, 5, 11), (5, 0, 17), (6, 9, 1),
              (7, 19, 37)],
    # from position 0, and to the last row of a block
    "from-zero": [(1, 0, 16), (3, 0, 32)],
    # behind a cached context that ends inside a block
    "behind-context": [(0, 21, 1), (3, 43, 21)],
    # slot 2 runs behind slot 0's first two blocks, which it shares
    "aliased-prefix": [(0, 30, 1), (2, 16, 24)],
}


def _dims(heads=4, kv_rank=96, rope=16, score_scale=1.0, q_rank=0):
    return A.MLADims(heads=heads, kv_rank=kv_rank, nope_dim=12,
                     rope_dim=rope, value_dim=10, q_rank=q_rank,
                     score_scale=score_scale)


def _step(dims, runs, seed=0, layer=1, dtype=np.float32):
    """A step's flat rows over a pool of two layers, the queries made by
    ``project`` from a hidden state (through a query latent where
    ``dims.q_rank``); slot 2 shares slot 0's first two blocks."""
    rng = np.random.default_rng(seed)
    width = -(-dims.row // 128) * 128
    rows = BLOCKS + 1
    pool = np.zeros((2 * rows, BS, width), np.float32)
    pool[..., :dims.row] = rng.standard_normal((2 * rows, BS, dims.row))
    tables = np.full((SLOTS, NB), -1, np.int32)
    free = list(rng.permutation(BLOCKS))
    for slot, seen, n in runs:
        need = -(-(seen + n) // BS)
        tables[slot, :need] = [free.pop() for _ in range(need)]
    if tables[0, 1] >= 0 and tables[2, 1] >= 0:
        tables[2, :2] = tables[0, :2]
    slot_of, pos_of = [], []
    for slot, seen, n in runs:
        slot_of += [slot] * n
        pos_of += list(range(seen, seen + n))
    valid = np.arange(T) < len(slot_of)
    assert valid[-PAD:].sum() == 0
    slot_of += [0] * (T - len(slot_of))
    pos_of += [0] * (T - len(pos_of))
    dm, H = 24, dims.heads
    qk = dims.nope_dim + dims.rope_dim

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * shape[0] ** -0.5)

    ap = {"w_kva": w(dm, dims.row), "c_norm": jnp.ones(dims.kv_rank),
          "w_kvb": w(dims.kv_rank, H, dims.nope_dim + dims.value_dim)}
    if dims.q_rank:
        # a model with a query latent keeps its matrices flat
        ap.update(wq_a=w(dm, dims.q_rank), q_norm=jnp.ones(dims.q_rank),
                  wq_b=w(dims.q_rank, H * qk),
                  w_kvb=ap["w_kvb"].reshape(dims.kv_rank, -1))
    else:
        ap["wq"] = w(dm, H, qk)
    h = jnp.asarray(rng.standard_normal((T, dm)).astype(np.float32))
    cos = jnp.asarray(np.cos(rng.uniform(0, 3, (64, dims.rope_dim // 2))),
                      jnp.float32)
    sin = jnp.sqrt(1 - cos * cos)
    q_n, q_r, _ = A.project(ap, h, cos, sin, jnp.asarray(pos_of, jnp.int32),
                            dims, 1e-5, lambda x, m: jnp.tensordot(x, m, 1))
    dt = jnp.dtype(dtype)
    q_n, q_r = q_n.astype(dt), q_r.astype(dt)
    return dict(pool=jnp.asarray(pool).astype(dt), layer=(layer * rows, rows),
                slot=jnp.asarray(slot_of, jnp.int32),
                pos=jnp.asarray(pos_of, jnp.int32), valid=jnp.asarray(valid),
                tables=jnp.asarray(tables), q_n=q_n, q_r=q_r, ap=ap,
                qf=A.fold_query(ap, q_n, q_r, dims), runs=runs)


def _tiles(dims, st, wide=WIDE):
    return A.latent_tiles(
        st["slot"], st["pos"], st["valid"], st["tables"], BS, NB,
        trash=st["layer"][1] - 1, heads=dims.heads, heights=HEIGHTS,
        wide=(A.expand_from(dims), wide))


@functools.partial(jax.jit, static_argnames=("dims", "wide", "rows", "most",
                                              "fold"))
def _calls(dims, st, wide, rows, most, fold):
    """(one program a ``dims`` and tile: the steps share their shapes;
    ``wide``, ``rows``, ``most``: ``WIDE``, ``WIDE_ROWS`` and
    ``WIDE_HEADS`` as the caller patched them, for the cache; without
    ``fold`` the folded calls' rows stay zero)"""
    assert (A.WIDE, A.WIDE_ROWS, A.WIDE_HEADS) == (wide, rows, most)
    tiles = _tiles(dims, st, wide)
    o = jnp.zeros(st["qf"].shape[:2] + (dims.kv_rank,), st["pool"].dtype)
    if fold:
        o = A.latent_attend_tiles(st["pool"], st["qf"], tiles, dims,
                                  st["layer"], HEIGHTS)
    folded = A.unfold_output(st["ap"], o, dims, st["pool"].dtype)
    return tiles, folded, A.latent_attend_expanded(
        st["pool"], st["q_n"], st["q_r"], A.w_kvb(st["ap"], dims),
        tiles.wide, folded, dims, st["layer"])


def _kernel(dims, st, wide=WIDE, rows=ROWS, fold=True):
    """The three calls one behind another → (tiles, the folded calls'
    rows unfolded, with the expanded call's laid over), at small tiles:
    the module's constants patched for the trace."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(A, "WIDE", wide)
        patch.setattr(A, "WIDE_ROWS", rows)
        tiles, folded, out = _calls(
            dims, {k: v for k, v in st.items() if k != "runs"}, wide, rows,
            A.WIDE_HEADS, fold)
    return tiles, np.asarray(folded), np.asarray(out)


def _expanded_alone(dims, st, **tile):
    """The expanded call alone → (its rows, which rows those are)."""
    _, _, out = _kernel(dims, st, fold=False, **tile)
    rows = _expanded_rows(dims, st)
    assert rows.any() and not out[~rows].any()
    return out[rows], rows


def _layer_rows(st):
    base, rows = st["layer"]
    t = st["tables"][st["slot"]]
    return jnp.where(t < 0, rows - 1, t) + base                # [T, NB]


def _xla(dims, st):
    """``latent_attend`` with a group a row, unfolded."""
    qpos = jnp.where(st["valid"], st["pos"], -1)
    o = A.latent_attend(st["pool"], st["qf"][:, None], qpos[:, None],
                        _layer_rows(st), dims, blocks=2)[:, 0]
    return np.asarray(A.unfold_output(st["ap"], o, dims, jnp.float32))


def _dense(dims, st):
    """Keys and values expanded for every cached row, a full masked
    score matrix a token, float32 → the heads' values [T, H, V]."""
    ctx = np.asarray(st["pool"], np.float32)[
        np.asarray(_layer_rows(st))].reshape(T, NB * BS, -1)
    c, k_r = ctx[..., :dims.kv_rank], ctx[..., dims.kv_rank:dims.row]
    w = np.asarray(A.w_kvb(st["ap"], dims))
    kv = np.einsum("tjc,chx->tjhx", c, w)
    k_n, v = kv[..., :dims.nope_dim], kv[..., dims.nope_dim:]
    s = (np.einsum("thn,tjhn->thj", np.asarray(st["q_n"], np.float32), k_n)
         + np.einsum("thr,tjr->thj", np.asarray(st["q_r"], np.float32), k_r)
         ) * dims.scale
    keep = np.arange(NB * BS)[None, :] <= np.asarray(st["pos"])[:, None]
    s = np.where(keep[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("thj,tjhv->thv", p, v)


def _expanded_rows(dims, st):
    """The rows of the runs long enough to expand, from the runs."""
    at, rows = 0, np.zeros(T, bool)
    for _, _, n in st["runs"]:
        rows[at:at + n] = n >= A.expand_from(dims)
        at += n
    return rows


@pytest.fixture(scope="module", params=sorted(STEPS))
def case(request):
    dims = _dims()
    st = _step(dims, STEPS[request.param], seed=len(request.param))
    return (dims, st) + _kernel(dims, st)


def test_each_row_is_taken_by_exactly_one_call(case):
    dims, st, tiles, folded, out = case
    assert A.expand_from(dims) == 16
    taken = np.zeros(T, int)
    for tl in tiles:
        for k in range(int(tl.count)):
            taken[int(tl.row[k]):int(tl.row[k]) + int(tl.length[k])] += 1
    valid = np.asarray(st["valid"])
    assert (taken == valid).all()
    wide = _expanded_rows(dims, st)
    n = int(tiles.wide.count)
    # (a run is cut where the batch's rows pass a multiple of WIDE)
    assert n == tile_counts([n for _, _, n in st["runs"]], *HEIGHTS,
                            wide=(16, WIDE))[3] >= wide.any() + (
        st["runs"] is STEPS["mixed"])
    assert int(np.asarray(tiles.wide.length)[:n].sum()) == wide.sum() > 0
    # the folded calls left the expanded call's rows alone, and the
    # expanded call theirs
    assert (folded[wide] == 0).all() and out[wide].any(axis=(1, 2)).all()
    np.testing.assert_array_equal(out[~wide], folded[~wide])


def test_expanded_call_matches_the_xla_formulation(case):
    dims, st, _, _, out = case
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(out[valid], _xla(dims, st)[valid],
                               rtol=1e-4, atol=1e-4)


def test_expanded_call_matches_dense_softmax_over_expanded_rows(case):
    dims, st, _, _, out = case
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(out[valid], _dense(dims, st)[valid],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", ["mixed", "from-zero"])
def test_the_steps_call_is_the_three_calls(name):
    """``latent_attend_runs`` (what ``inference/model.py`` calls: the
    folded products made for the rows that take the folded form, the
    run call under a ``cond`` on its list) against the three calls one
    behind another over the step's rows: a step with a folded run and
    one without."""
    dims = _dims()
    st = _step(dims, STEPS[name], seed=len(name))
    tiles, _, out = _kernel(dims, st)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(A, "WIDE", WIDE)
        patch.setattr(A, "WIDE_ROWS", ROWS)
        got = jax.jit(lambda st: A.latent_attend_runs(
            st["ap"], st["pool"], st["q_n"], st["q_r"], _tiles(dims, st),
            dims, st["layer"], st["pool"].dtype, HEIGHTS))(
                {k: v for k, v in st.items() if k != "runs"})
    assert (int(tiles.long.count) > 0) == (name == "mixed")
    np.testing.assert_allclose(np.asarray(got), out, rtol=1e-6, atol=1e-6)


def test_rows_that_pad_the_step_are_left_zero(case):
    _, st, _, _, out = case
    assert not np.asarray(st["valid"])[-PAD:].any()
    assert (out[-PAD:] == 0).all()


def test_aliased_prefix_is_read_where_it_lies():
    dims = _dims()
    st = _step(dims, STEPS["aliased-prefix"], seed=14)
    tables = np.asarray(st["tables"])
    assert (tables[2, :2] == tables[0, :2]).all()
    tiles, _, _ = _kernel(dims, st)
    assert (int(tiles.wide.pos[0]), int(tiles.wide.length[0])) == (16, 24)
    assert (np.asarray(tiles.wide.tables)[0, :2] == tables[0, :2]).all()


@pytest.mark.parametrize("dims", [
    _dims(score_scale=1.5896), _dims(q_rank=24, score_scale=0.8),
    _dims(heads=2, kv_rank=192, rope=32)],
    ids=["yarn", "query-latent", "rows-of-256"])
def test_other_layers(dims):
    """A multiplier on the softmax scale, a query latent (``W_kvb`` kept
    as a matrix), rows of 256 lanes."""
    st = _step(dims, STEPS["mixed"], seed=dims.heads + dims.q_rank)
    out, rows = _expanded_alone(dims, st)
    np.testing.assert_allclose(out, _dense(dims, st)[rows],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out, _xla(dims, st)[rows],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads,most,group", [
    (4, 16, 4), (8, 16, 8), (8, 4, 4), (8, 3, 2), (6, 4, 3), (5, 4, 1),
    (128, 16, 16), (64, 16, 16), (32, 16, 16)])
def test_head_groups(monkeypatch, heads, most, group):
    """A tile holds the largest divisor of the heads that ``WIDE_HEADS``
    allows: every head count is handled, whatever divides it, and a tile
    of fewer heads than the layer's walks its blocks once a group (run
    where the groups are two and where they are uneven in the cap)."""
    monkeypatch.setattr(A, "WIDE_HEADS", most)
    dims = _dims(heads=heads)
    assert A.wide_heads(dims) == group
    if (heads, most) not in ((8, 4), (6, 4)):
        return
    st = _step(dims, STEPS["mixed"], seed=heads)
    out, rows = _expanded_alone(dims, st)
    np.testing.assert_allclose(out, _dense(dims, st)[rows],
                               rtol=1e-4, atol=1e-4)


def test_bf16_rows_and_float32_statistics():
    """The stored type: bf16 rows, queries, keys and values and their
    products, float32 scores and accumulator."""
    dims = _dims()
    st = _step(dims, STEPS["mixed"], seed=9, dtype=jnp.bfloat16)
    out, rows = _expanded_alone(dims, st)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               _dense(dims, st)[rows], rtol=3e-2, atol=3e-2)


def test_the_kernels_own_tile():
    """At ``WIDE`` and ``WIDE_ROWS`` (a run is one tile of one row-tile
    here) against the small tiles'."""
    dims = _dims()
    st = _step(dims, STEPS["mixed"], seed=7)
    small, _ = _expanded_alone(dims, st)
    own, _ = _expanded_alone(dims, st, wide=A.WIDE, rows=A.WIDE_ROWS)
    np.testing.assert_allclose(own, small, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dims,rows", [
    (A.MLADims(128, 512, 128, 64, 128), 171),
    (A.MLADims(64, 512, 128, 64, 128, q_rank=1536, q_scale=2.0), 171),
    (A.MLADims(32, 512, 128, 64, 128, score_scale=1.3), 171),
    (_dims(), 13), (_dims(heads=2, kv_rank=192, rope=32), 12),
    # a latent no larger than a head's key and value: never
    (A.MLADims(4, 16, 16, 8, 16), None)])
def test_threshold_is_a_function_of_the_layers_sizes(dims, rows):
    """The break-even of the two forms' operations, whatever the head
    count, the query's path or the scale; times ``EXPAND_MARGIN``, up to
    whole vectors of 8 rows."""
    if rows is None:
        assert A.expand_from(dims) == A.NEVER
        return
    per_pair = dims.row + dims.kv_rank - (dims.nope_dim + dims.rope_dim
                                          + dims.value_dim)
    assert -(-dims.kv_rank * (dims.nope_dim + dims.value_dim)
             // per_pair) == rows
    want = -(-int(dims.kv_rank * (dims.nope_dim + dims.value_dim) / per_pair
                  * A.EXPAND_MARGIN + 0.5) // 8) * 8
    assert A.expand_from(dims) == want
    assert A.expand_from(dims._replace(heads=dims.heads * 2, q_rank=7,
                                       q_scale=3.0, score_scale=2.0)) == want
    assert 2 * want <= A.WIDE


@pytest.mark.parametrize("n,expanded", [(15, False), (16, True), (17, True),
                                        (1, False)])
def test_a_run_one_row_under_the_threshold_takes_the_folded_call(n, expanded):
    dims = _dims()
    assert A.expand_from(dims) == 16
    st = _step(dims, [(1, 9, n), (4, 3, 1)], seed=n)
    tiles, folded, out = _kernel(dims, st)
    assert int(tiles.wide.count) == int(expanded)
    assert int(tiles.long.count) == (0 if expanded or n == 1 else -(-n // 4))
    assert folded[:n].any() != expanded
    valid = np.asarray(st["valid"])
    np.testing.assert_allclose(out[valid], _dense(dims, st)[valid],
                               rtol=1e-4, atol=1e-4)
    assert tile_counts([n, 1], *HEIGHTS, wide=(16, WIDE)) == (
        1 + (n == 1), int(tiles.long.count),
        0 if expanded or n == 1 else n, int(expanded))


def test_a_step_too_small_for_a_long_run_has_no_third_list():
    dims = _dims()
    st = _step(dims, [(1, 9, 5), (4, 3, 1)])
    st = {k: st[k] for k in ("slot", "pos", "valid", "tables", "layer")}
    few = {k: st[k][:15] for k in ("slot", "pos", "valid")}
    assert jax.eval_shape(lambda st: _tiles(dims, st),
                          dict(st, **few)).wide is None
    assert jax.eval_shape(lambda st: _tiles(dims, st),
                          st).wide.row.shape == (T // 16 + -(-T // WIDE) - 1,)


@pytest.mark.parametrize("lengths,want", [
    ([1, 1, 464, 1], (3, 0, 0, 1)), ([200, 176, 100, 1], (1, 13, 100, 2)),
    ([175, 520], (0, 22, 175, 2))])
def test_tile_counts_with_a_third_list(lengths, want):
    assert tile_counts(lengths, 1, 8, wide=(176, 512)) == want


# --- the host's counts ----------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    """A tiny latent model whose latent is large beside a head's key and
    value, so that a prompt of 16 rows expands; the Pallas calls
    interpreted."""
    from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
    from deepspeed_tpu.models import Model
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.models.transformer import init_params

    cfg = build_config("longcat-tiny", mla_kv_rank=32, mla_nope_dim=8,
                       mla_value_dim=8, experts_held=None)
    axes = {}

    def init(key):
        params, axes["axes"] = init_params(cfg, key)
        return params

    params = jax.jit(init)(jax.random.PRNGKey(3))

    def make(attn_impl):
        return InferenceEngine(
            Model.from_params(cfg, params, param_axes=axes["axes"]),
            InferenceConfig(token_budget=48, max_seqs=4, kv_block_size=8,
                            num_kv_blocks=24, max_seq_len=192, trace=True,
                            attn_impl=attn_impl, param_dtype=jnp.float32,
                            kv_dtype=jnp.float32))

    return cfg, make


def test_engine_counts_the_expanded_runs_and_serves_the_same_tokens(engine):
    """``ds.serve.stage`` of a step with a run of 23 rows (expanded), one
    of 9 (folded) and, later, a run of 20 behind 23 cached rows; the
    tiles' third label; and the tokens of the engine that runs the XLA
    formulation."""
    from deepspeed_tpu.inference import SamplingParams

    cfg, make = engine
    assert A.expand_from(cfg.mla_dims) == 16
    rng = np.random.default_rng(2)
    prompts = {1: rng.integers(0, 1024, 9).tolist(),
               2: rng.integers(0, 1024, 23).tolist()}
    greedy = SamplingParams(temperature=0.0, max_new_tokens=3)
    eng = make("pallas")
    got = eng.generate(prompts, greedy)
    stages = [e["args"] for e in eng.tracer.events()
              if e["name"] == "ds.serve.stage"]
    first = [st for st in stages if st["n_tokens"] == 32][0]
    assert first["latent_pairs"] == 9 * 10 // 2 + 23 * 24 // 2
    assert first["latent_pairs_expanded"] == 23 * 24 // 2
    assert first["latent_rows_expanded"] == 23
    assert (first["n_tiles_one"], first["n_tiles_run"],
            first["n_tiles_expanded"]) == (0, 1, 1)
    decode = [st for st in stages if st["n_tokens"] == 2][0]
    assert (decode["latent_pairs_expanded"], decode["latent_rows_expanded"],
            decode["n_tiles_expanded"]) == (0, 0, 0)
    tiles = eng.metrics.snapshot()["serving_attn_tiles_total"]
    assert tiles['{height="expanded"}'] == 1
    assert tiles['{height="run"}'] == 1
    xla = make("xla")
    assert xla.generate(prompts, greedy) == got
    assert not any("latent_pairs_expanded" in e["args"]
                   for e in xla.tracer.events()
                   if e["name"] == "ds.serve.stage")


def test_count_attn_kv_against_a_hand_count(engine, monkeypatch):
    """``_count_attn_kv`` over a schedule: a run of 20 rows behind 23
    cached ones expands (20 x 23 + 20 x 21 / 2 pairs over 43 rows), a run
    of 15 does not."""
    import types

    cfg, make = engine
    eng = make("pallas")
    monkeypatch.setattr(eng.state, "seqs",
                        {7: types.SimpleNamespace(seen_tokens=23)})
    sched = [(7, list(range(20))), (8, list(range(15))), (9, [3])]
    args = eng._count_attn_kv(sched, True)
    assert args["latent_pairs"] == 20 * 23 + 210 + 120 + 1
    assert args["latent_pairs_expanded"] == 20 * 23 + 210
    assert args["latent_rows_expanded"] == 43
    assert args["latent_tokens"] == 43 + 15 + 1
    # an engine that runs the XLA formulation expands nothing
    assert "latent_pairs_expanded" not in eng._count_attn_kv(sched, False)
