"""OLMoE on the normal path, at ``olmoe-tiny`` on seeded float32 weights,
against the plain reference's own mathematics
(``benchmarks/reference/olmoe-1b-7b-d10.py``, loaded by path: float32
``jax.numpy``, every expert computed densely and masked to each token's
top-k, one RMSNorm over the whole q and the whole k projection).

* serving: logits after the prompt and after each of several tokens fed
  through the paged cache;
* training: ``Model.apply``'s logits (QK-norm in ``block_apply``);
* serving is dropless: a sequence's logits are the same bits alone and
  beside rows built to overload its experts, which the capacity form
  serving used to take does not give;
* the rows that pad a step's bucket are routed nowhere.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.drivers.serve import engine_logits
from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model, init_params
from deepspeed_tpu.parallel import moe as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "reference", "olmoe-1b-7b-d10.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(seed, **over):
    cfg = build_config("olmoe-tiny", **over)
    params, axes = init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params, Model.from_params(cfg, params, param_axes=axes)


def ref_config(cfg):
    return {"rms_norm_eps": cfg.eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.moe_top_k,
            "num_hidden_layers": cfg.num_layers}


def engine(model, attn_impl="xla"):
    return InferenceEngine(model, InferenceConfig(
        token_budget=64, max_seqs=8, kv_block_size=16, num_kv_blocks=64,
        max_seq_len=128, attn_impl=attn_impl, param_dtype=jnp.float32,
        kv_dtype=jnp.float32))


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("seed", [3, 11])
def test_serving_matches_the_reference_through_the_cache(ref, seed, attn_impl):
    cfg, params, model = tiny(seed)
    rng = np.random.default_rng(seed)
    seqs = {1: rng.integers(0, cfg.vocab_size, 23).tolist(),
            2: rng.integers(0, cfg.vocab_size, 41).tolist()}
    n_prompt = {1: 18, 2: 35}
    eng = engine(model, attn_impl)
    # the benchmark's own driver of the paged path: prompts prefilled in
    # one step, the rest fed one token a step through the cache
    got = engine_logits(eng, seqs, n_prompt, eng.max_blocks_per_seq)
    for u, s in seqs.items():
        want = np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg))
                          )[n_prompt[u] - 1:]
        assert len(got[u]) == len(want) >= 6
        # float32 both sides: what is left is the order of summation
        np.testing.assert_allclose(np.stack(got[u]), want, rtol=0,
                                   atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("over", [dict(moe_dispatch="ragged"),
                                  dict(moe_dispatch="scatter",
                                       capacity_factor=8.0)],
                         ids=["ragged", "scatter-no-drop"])
def test_training_forward_matches_the_reference(ref, over):
    cfg, params, model = tiny(5, **over)
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 33))
    got = np.asarray(model.apply(params, jnp.asarray(ids)))
    for b in range(2):
        want = np.asarray(ref.logits(params, ids[b], ref_config(cfg)))
        np.testing.assert_allclose(got[b], want, rtol=0,
                                   atol=2e-5 * np.abs(want).max())


def test_qk_norm_is_over_the_whole_projection(ref):
    """Not a norm per head: the mean square is taken over all heads."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 16)) \
        * jnp.arange(1, 5)[None, :, None]
    scale = jax.random.uniform(jax.random.PRNGKey(1), (4, 16)) + 0.5
    got = L.qk_rmsnorm(scale, x, 1e-5)
    want = ref._rms(x.reshape(5, 64), scale.reshape(64), 1e-5)
    np.testing.assert_allclose(got.reshape(5, 64), want, rtol=1e-6)
    per_head = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
        * scale
    assert float(jnp.abs(per_head - got).max()) > 0.1
    # and a forward that leaves it out is told apart on seeded weights
    cfg, params, model = tiny(2)
    _, _, plain = tiny(2, qk_norm=False)
    ids = jnp.arange(12)[None] % cfg.vocab_size
    a, b = model.apply(params, ids), plain.apply(params, ids)
    assert float(jnp.abs(a - b).max()) > 0.05 * float(jnp.abs(a).max())


def _experts(seed=0, E=8, d=32, w=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    gate, _ = M.gate_init(ks[0], d, E)
    gate = {"kernel": gate["kernel"] * 50.0}       # decisive routing
    experts, _ = M.experts_init(ks[1], E, d, w, gated=True)
    return gate, experts


def _serve(gate, experts, h, valid=None, top_k=4, kernel=False):
    return M.moe_serve(gate, experts, h, valid, top_k=top_k,
                       activation=jax.nn.silu, gated=True, norm_topk=False,
                       kernel=kernel)


@pytest.mark.parametrize("sizes,m,tm", [
    ([10, 0, 30, 7], 64, 16),       # an empty group, rows in no group
    ([0, 0, 0, 0], 32, 16),         # nothing routed at all
    ([16, 16, 16], 48, 16),         # groups on tile edges, all rows taken
    ([1, 1, 1, 1, 1], 40, 8),       # several groups in one tile
    ([3, 50], 53, 16),              # a group over four tiles; m padded
], ids=["empty-group", "nothing", "aligned", "crowded-tile", "long-group"])
def test_grouped_matmul_kernel_is_ragged_dot(sizes, m, tm):
    """The Pallas kernel (interpret mode here) against
    ``jax.lax.ragged_dot``; rows past the last group come back zero."""
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    rng = np.random.default_rng(len(sizes))
    x = jnp.asarray(rng.normal(size=(m, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), 32, 128)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(grouped_matmul(x, w, gs, tm=tm))
    n = sum(sizes)
    np.testing.assert_allclose(got[:n],
                               np.asarray(jax.lax.ragged_dot(x, w, gs))[:n],
                               rtol=1e-5, atol=1e-5)
    assert got.shape == (m, 128) and not got[n:].any()


@pytest.mark.parametrize("n_real", [3, 16])
def test_expert_layer_is_the_same_through_the_kernel(n_real):
    gate, experts = _experts(2)
    h = jax.random.normal(jax.random.PRNGKey(5), (16, 32))
    valid = jnp.arange(16) < n_real
    a, sa = _serve(gate, experts, h, valid)
    b, sb = _serve(gate, experts, h, valid, kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


@pytest.mark.parametrize("crowd", [0, 7, 40])
def test_a_rows_output_does_not_depend_on_its_neighbours(crowd):
    """The dropless property: one row alone, and the same row among
    ``crowd`` copies of a row that takes the same experts (so each of
    its experts is asked for ``crowd + 1`` rows, far over its capacity in
    the old form), give the same bits."""
    gate, experts = _experts()
    row = jax.random.normal(jax.random.PRNGKey(7), (1, 32))
    others = jax.random.normal(jax.random.PRNGKey(8), (6, 32))
    alone, _ = _serve(gate, experts, jnp.concatenate([row, others]),
                      top_k=2)
    # the crowd: small perturbations of ``row``, same two experts
    near = row + 1e-4 * jax.random.normal(jax.random.PRNGKey(9), (crowd, 32))
    h = jnp.concatenate([row, others, near])
    many, stats = _serve(gate, experts, h, top_k=2)
    assert int(stats[0]) == h.shape[0] * 2          # nothing dropped
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(many[0]))
    if crowd == 40:
        # what serving used to run: capacity ceil(T * k * 2.0 / E) slots
        # an expert, so the crowd pushes assignments out
        old, _ = M.moe_ffn(gate, experts, h[None], top_k=2,
                           capacity_factor=2.0, activation=jax.nn.silu,
                           gated=True, norm_topk=False)
        assert float(jnp.abs(old[0] - many).max()) > 1e-3
        assert int(stats[1]) > 3000     # an expert with thrice the mean


@pytest.mark.parametrize("n_real", [1, 5, 16])
def test_padding_rows_are_routed_nowhere(n_real):
    gate, experts = _experts(1)
    T = 16
    real = jax.random.normal(jax.random.PRNGKey(3), (n_real, 32))
    valid = jnp.arange(T) < n_real
    outs = []
    for junk in (0.0, 1.0, 1e6):
        h = jnp.concatenate([real, jnp.full((T - n_real, 32), junk)])
        y, stats = _serve(gate, experts, h, valid)
        # counted in no group: only the real rows' assignments
        assert int(stats[0]) == n_real * 4
        assert not np.asarray(y[n_real:]).any()
        outs.append(np.asarray(y[:n_real]))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    full, _ = _serve(gate, experts, real)      # another shape: not bits
    np.testing.assert_allclose(outs[0], np.asarray(full), rtol=1e-4,
                               atol=1e-6)


def _combine_by_rows(gate, experts, h, valid, top_k, held, zero):
    """``moe_serve`` as a float32 loop over rows and choices: (y, stats,
    taken) from the router's own choice (``M.route``, not under test)."""
    outputs = gate["kernel"].shape[-1]
    first, count = held or (0, outputs - zero)
    hf = np.asarray(h, np.float32)
    vals, ids, _ = M.route(jnp.dot(h, gate["kernel"].astype(h.dtype),
                                   preferred_element_type=jnp.float32),
                           top_k=top_k, norm_topk=True)
    vals, ids = np.asarray(vals), np.asarray(ids)
    wi, wg, wo = (np.asarray(experts[k], np.float32)
                  for k in ("wi", "wg", "wo"))
    y = np.zeros_like(hf)
    rows = np.zeros(count, np.int64)
    nothing = 0
    taken = np.full(ids.shape, outputs, np.int32)
    for t in np.flatnonzero(np.asarray(valid)):
        taken[t] = ids[t]
        for w, e in zip(vals[t], ids[t]):
            if e >= outputs - zero:         # computes nothing: the input
                y[t] += w * hf[t]
                nothing += 1
            elif first <= e < first + count:
                g = hf[t] @ wg[e - first]
                y[t] += w * ((g / (1 + np.exp(-g)) * (hf[t] @ wi[e - first]))
                             @ wo[e - first])
                rows[e - first] += 1
    n = int(rows.sum())
    stats = [n, int(rows.max()) * 1000 * count // max(n, 1),
             int((rows > 0).sum())] + ([nothing] if zero else [])
    return y, np.asarray(stats), taken


@pytest.mark.parametrize("zero", [0, 4])
@pytest.mark.parametrize("share", [None, (4, 6)], ids=["whole", "share"])
@pytest.mark.parametrize("top_k", [3, 8, 10, 12])
def test_combine_is_a_weighted_sum_over_a_rows_own_choices(top_k, share,
                                                           zero):
    """The choice-major order and the sum over ``K`` slabs against a
    loop over rows and choices, at ``top_k`` on and off the tiling's 8:
    a share of the experts held, experts that compute nothing, padding
    rows inside the step and at its end.  The whole layer on float32
    rows, a share of it on bfloat16 rows (as the cells hold one)."""
    outputs, d, T = 16, 32, 24
    dt, eps = (jnp.float32, 1e-6) if share is None else (jnp.bfloat16,
                                                         2.0 ** -8)
    gate, experts = _experts(top_k, E=outputs, d=d)
    first, count = share or (0, outputs - zero)
    experts = jax.tree.map(lambda w: w[first:first + count], experts)
    valid = (jnp.arange(T) < T - 4) & (jnp.arange(T) != 3)
    h = jax.random.normal(jax.random.PRNGKey(top_k), (T, d)).astype(dt)
    y, stats, taken = jax.jit(lambda h: M.moe_serve(
        gate, experts, h, valid, top_k=top_k, activation=jax.nn.silu,
        gated=True, norm_topk=True, held=share, zero=zero,
        with_ids=True))(h)
    want, want_stats, want_taken = _combine_by_rows(
        gate, experts, h, valid, top_k, share, zero)
    assert y.dtype == dt
    y = np.asarray(y, np.float32)
    np.testing.assert_allclose(y, want, rtol=8 * eps,
                               atol=8 * eps * np.abs(want).max())
    np.testing.assert_array_equal(np.asarray(stats), want_stats)
    np.testing.assert_array_equal(np.asarray(taken), want_taken)
    assert not y[~np.asarray(valid)].any()


def test_served_sequence_is_bit_equal_alone_and_in_a_crowd():
    """Through ``ragged_forward``: a prompt prefilled alone, and beside
    seven prompts of one repeated token (every row of theirs asks for
    the same experts), gives the same logits, bit for bit."""
    cfg, params, model = tiny(4)
    mine = np.random.default_rng(4).integers(0, cfg.vocab_size, 7).tolist()

    def prefill(crowd):
        eng = engine(model)
        step = eng._build_step(eng.max_blocks_per_seq)
        eng.put(1, mine)
        for j in range(crowd):
            eng.put(100 + j, [5] * 8)
        sched = eng._schedule()
        assert len(sched) == 1 + crowd
        batch = eng._stage(eng.state.build_batch(sched,
                                                 eng.icfg.token_budget))
        assert int(np.asarray(batch.token_valid).sum()) == 7 + 8 * crowd
        logits, _ = step(eng.params, eng._quant, eng.state.kv, batch)
        return np.asarray(logits[eng.state.slot(1)])

    np.testing.assert_array_equal(prefill(0), prefill(7))


def test_step_reports_its_routing_statistics():
    """The serving step appends (assignments over the layers, 1000 x the
    worst layer's fullest expert over the mean) to its sampled tokens,
    and the engine's counters read them from that one readback."""
    from deepspeed_tpu.inference import SamplingParams
    from deepspeed_tpu.inference.model import MOE_STAT_ROWS
    cfg, _, model = tiny(6)
    eng = engine(model)
    assert eng._zero_toks.shape == (8 + MOE_STAT_ROWS,)
    eng.put(1, list(range(10)))
    eng.put(2, list(range(3)))
    out = eng.step(sampling=SamplingParams(temperature=0.0,
                                           max_new_tokens=4))
    assert sorted(out) == [1, 2]
    snap = eng.metrics_snapshot()
    assert snap["serving_moe_assignments_total"] == \
        13 * cfg.moe_top_k * cfg.num_layers
    load = snap["serving_moe_expert_load_max_over_mean"]
    assert 1.0 <= load <= cfg.num_experts
    _, _, dense = tiny(6, num_experts=1)
    assert "serving_moe_assignments_total" not in \
        engine(dense).metrics_snapshot()
