"""A layer that holds ONE kind of cache (``granite-h-tiny``: a Mamba-2
mixer's state rows in most layers, one attention layer's blocks, no
positions) through the engine, against the benchmark's own plain reference
(held to ``transformers`` in ``tests/test_granite_reference.py``): prefill
in one step, cut over steps and chunks, decode through both caches, on
both attention formulations; a slot taken again; a period of four and one
cut short (the preset's six layers); the wrong forwards only a float32
comparison tells; the pools sized by the layers that use them; the spans,
counters and gauges, with a share of the experts held."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.models.presets import build_config
from deepspeed_tpu.models.transformer import Model
from test_falcon_h1 import GREEDY, TOL, paged_logits, rel, state_kernels
from test_granite_reference import ref, ref_config, tiny  # noqa: F401


def engine(tiny, **over):
    cfg, params, axes = tiny
    kw = dict(token_budget=20, max_seqs=4, kv_block_size=8,
              num_kv_blocks=64, max_seq_len=128, attn_impl="xla",
              param_dtype=jnp.float32, kv_dtype=jnp.float32)
    kw.update(over)
    return InferenceEngine(Model.from_params(cfg, params, param_axes=axes),
                           InferenceConfig(**kw))


@pytest.fixture(scope="module")
def seqs(tiny):
    cfg = tiny[0]
    rng = np.random.default_rng(0)
    # prompts that neither the step's 20 tokens nor the mixer's chunk of
    # 8 divide, and one that a single step holds; then 8 fed tokens
    lens = {1: 53, 2: 31, 3: 9}
    return ({u: rng.integers(0, cfg.vocab_size, n + 8).tolist()
             for u, n in lens.items()}, lens)


def built_once(eng):
    """``eng`` with ONE logits-returning step for all its uses here
    (``paged_logits`` builds, and so compiles, one a call)."""
    step = eng._build_step(eng.max_blocks_per_seq)
    eng._build_step = lambda *a, **k: step
    return eng


@pytest.fixture(scope="module")
def engines(tiny):
    return {impl: built_once(engine(tiny, attn_impl=impl))
            for impl in ("xla", "pallas")}


@pytest.fixture(scope="module")
def system_rows(tiny, engines, seqs):
    with jax.default_matmul_precision("highest"):
        out = {impl: paged_logits(eng, *seqs)
               for impl, eng in engines.items()}
        with state_kernels():   # an engine of its own: another program
            out["state kernels"] = paged_logits(engine(tiny), *seqs)
    for eng in engines.values():
        for u in seqs[0]:
            eng.flush(u)
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas", "state kernels"])
def test_prefill_and_decode_agree_with_the_reference(tiny, ref, seqs,
                                                     system_rows, impl):
    """Prefill in one step (uid 3), cut over steps and chunks (uids 1, 2),
    then decode through the state rows and the one layer's blocks."""
    cfg, params, _ = tiny
    tokens, n_prompt = seqs
    rows, scheds = system_rows[impl]
    for u, s in tokens.items():
        want = np.asarray(ref.logits(params, np.asarray(s), ref_config(cfg),
                                     last=9))
        got = np.stack(rows[u])
        assert got.shape == want.shape
        assert rel(got, want) < TOL, (impl, u)
    assert any((3, 9) in s for s in scheds)          # one step held it
    assert sum((1, 20) in s for s in scheds) >= 2    # several, the other
    assert any(sum(n > 1 for _, n in s) >= 2 for s in scheds)


def test_a_slot_taken_again_starts_from_zeros(tiny, ref, engines,
                                              system_rows):
    cfg, params, _ = tiny
    rng = np.random.default_rng(7)
    first = {11: rng.integers(0, cfg.vocab_size, 30).tolist()}
    second = {12: rng.integers(0, cfg.vocab_size, 17).tolist()}
    eng = engines["xla"]
    with jax.default_matmul_precision("highest"):
        eng.state._free_slots.sort()
        paged_logits(eng, first, {11: 25})
        slot = eng.state.slot(11)
        assert float(jnp.abs(eng.state.kv["ssm"][:, slot]).max()) > 0
        eng.flush(11)
        eng.state._free_slots.sort()
        rows, _ = paged_logits(eng, second, {12: 9})
    assert eng.state.slot(12) == slot
    c = ref_config(cfg)
    want = np.asarray(ref.logits(params, np.asarray(second[12]), c, last=9))
    assert rel(np.stack(rows[12]), want) < TOL
    # the control: a state and a tail kept would not pass
    stale = np.asarray(ref.logits(params, np.asarray(second[12]), c, last=9,
                                  wrong="old_state"))
    assert rel(stale, want) > 100 * TOL


def test_every_wrong_forward_fails_the_tolerance(tiny, ref, seqs,
                                                 system_rows):
    """What the chip's comparison has to tell, told here in float32: the
    true forward passes ``TOL`` above, each control reads far over it."""
    cfg, params, _ = tiny
    tokens, _ = seqs
    got = np.stack(system_rows["xla"][0][1])
    s = np.asarray(tokens[1])
    at = {"state_reset": "@53", "no_tail": "@40"}
    # (all twelve: the rehearsal and ``chip_smoke.py``; here those that
    # lie at a step's or a chunk's boundary, and two of the multipliers)
    for wrong in ("no_tail", "state_reset", "no_residual_scale", "rope"):
        bad = np.asarray(ref.logits(params, s, ref_config(cfg),
                                    wrong=wrong + at.get(wrong, ""),
                                    last=9))
        assert rel(got, bad) > 50 * TOL, wrong


# ---- the pools, the spans and the counters ----------------------------

def held_engine(tiny, **kw):
    """The tiny model holding experts 0-3 of its eight."""
    cfg, params, axes = tiny
    held = build_config("granite-h-tiny", experts_held=(0, 4))
    p2 = dict(params, blocks=dict(params["blocks"], experts=jax.tree.map(
        lambda a: a[:, :4], params["blocks"]["experts"])))
    return held, p2, engine((held, p2, axes), **kw)


def test_pools_are_sized_by_the_layers_that_use_them(tiny):
    """State rows for the five Mamba layers, blocks for the one attention
    layer, and no others; what the engine's contracts resolve to follows
    from the model."""
    cfg = tiny[0]
    eng = engine(tiny)                  # prefix_cache, spec_decode: auto
    assert not eng.state.prefix_cache and eng._spec is None
    assert set(eng.state.kv) == {"kv", "ssm", "conv"}
    sd = cfg.ssm_dims
    assert eng.state.kv["ssm"].shape == (5, 5, sd.heads, sd.head_dim,
                                         sd.state)
    assert eng.state.kv["conv"].shape == (5, 5, sd.conv, sd.conv_channels)
    assert eng.state.kv["kv"].shape == (1, 65, 8, 2, cfg.num_kv_heads,
                                        cfg.head_dim)
    assert eng._recurrent.layers == 5 and eng.state.cfg.num_layers == 1
    snap = eng.metrics.snapshot()
    assert snap["serving_state_rows_bytes"] \
        == 5 * 5 * 4 * (8 * 16 * 16 + 4 * 160)
    assert snap["serving_block_pool_bytes"] == 65 * 8 * 2 * 2 * 16 * 4
    for option in ("prefix_cache", "spec_decode", "kv_tier"):
        with pytest.raises(ValueError, match=option):
            engine(tiny, **{option: "on"})


def test_stage_and_readback_spans_and_counters(tiny):
    held, _, eng = held_engine(tiny, trace=True, token_budget=32,
                               attn_impl="pallas")
    rng = np.random.default_rng(2)
    eng.put(3, [5])
    eng.put(1, rng.integers(0, 1024, 9).tolist())
    eng.put(2, rng.integers(0, 1024, 40).tolist())
    out = eng.step(sampling=GREEDY)
    while eng.in_flight and not out:
        out = eng.step(sampling=GREEDY)
    ev = eng.tracer.events()
    stage = [e["args"] for e in ev if e["name"] == "ds.serve.stage"][0]
    # uid 3's one token is a run that starts at position 0 and advances
    # its state by one; uid 2 takes what the budget leaves
    assert (stage["state_rows"], stage["scan_tokens"],
            stage["state_starts"], stage["state_replays"]) == (1, 31, 3, 0)
    # the cached tokens the ONE attention layer reads: the sum of seen + n
    assert stage["kv_tokens_full"] == 1 + 9 + 22
    assert "latent_tokens" not in stage and "n_tiles_short" in stage
    back = [e["args"] for e in ev if e["name"] == "ds.serve.readback"][0]
    made = 32 * held.moe_top_k * held.num_layers
    assert back["moe_assignments_made"] == made
    assert 0 < back["moe_assignments"] < made
    assert 0 < back["moe_experts_touched"] <= held.num_layers * 4
    snap = eng.metrics.snapshot()
    asg = snap["serving_moe_assignments_total"]
    assert asg['{where="held"}'] + asg['{where="absent"}'] >= made
    assert snap["serving_attn_kv_tokens_total"]['{kind="full"}'] >= 32
    assert snap["serving_state_bytes"] \
        == 3 * eng._recurrent.bytes_per_seq(5)


def test_a_long_run_of_one_kind_is_a_rolled_loop(ref):
    """In a period of more than ``UNROLLED_PERIOD`` layers the layers of
    one kind in a row run as one traced layer in a loop (the published
    period's Mamba-2 x5 and x4): the same logits, through both caches,
    with the routing and its stats in the layers' order."""
    from test_granite_reference import seeded
    tiny = seeded(build_config(
        "granite-h-tiny", num_layers=10,
        layer_pattern=("mamba",) * 5 + ("full",) + ("mamba",) * 3))
    cfg, params, _ = tiny
    assert cfg.layer_plan == (0, 1, 1)
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, 31)
    eng = engine(tiny)
    with jax.default_matmul_precision("highest"):
        rows, _ = paged_logits(eng, {1: ids.tolist()}, {1: 27})
    want = np.asarray(ref.logits(params, ids, ref_config(cfg), last=5))
    assert rel(np.stack(rows[1]), want) < TOL
    text = eng._build_step(eng.max_blocks_per_seq, with_routing=True).lower(
        eng.params, eng._quant, eng.state.kv,
        eng._stage(eng.state.blank_batch(20))).as_text()
    # the period's scan and the run's inside it
    assert text.count("stablehlo.while") >= 2
