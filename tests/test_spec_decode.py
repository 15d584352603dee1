"""Model-free speculative decoding tests (docs/SERVING.md "Speculative
decoding"): the n-gram/prompt-lookup proposer, the StateManager draft
window + write-cursor rollback, config gating, and the exact-parity bar
— greedy and seeded generate() outputs must be token-identical with
``spec_decode`` on vs off across prefix cache on/off × pipeline depth
1/2 × preemption, with a stop token landing INSIDE an accepted draft
truncating exactly where the stepwise engine would have stopped.

Telemetry: drafted == accepted + rejected, and the per-request
drafted/accepted counts reconcile exactly with the engine counters
(the PR-5 by-construction accounting invariant, extended)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     NgramProposer, SamplingParams,
                                     StateManager, KVCacheConfig)
from deepspeed_tpu.models import build_model
from tests.serving_ref import strict_generate


@pytest.fixture(scope="module")
def model():
    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       max_seq_len=512)


def mk(model, **over):
    """fp32 engine (exact-parity tests: bf16 argmax near-ties are
    legitimately order-sensitive) with spec-friendly defaults."""
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=16,
              num_kv_blocks=64, kv_dtype=jnp.float32,
              param_dtype=jnp.float32, max_seq_len=256)
    kw.update(over)
    return InferenceEngine(model, InferenceConfig(**kw))


# a prompt whose n-grams recur: prompt-lookup territory (code/RAG-like)
REPETITIVE = [5, 6, 7, 8] * 6
MIXED = {0: list(REPETITIVE), 1: [9, 2, 9, 2, 9, 2, 44],
         2: [3, 1, 4, 1, 5, 9, 2, 6]}


def drive_full(eng, prompts, sp, rng=None, preempt=None):
    """The strict caller-fed loop, keeping EVERY emitted token (an
    accepted verify window emits several per step); ``preempt=(uid,
    after_n_steps)`` force-evicts mid-run like the overload suite."""
    def after_step(n):
        if preempt is not None and n == preempt[1] \
                and preempt[0] in eng.state.seqs:
            eng._preempt(preempt[0])

    return strict_generate(eng, prompts, sp, rng=rng, after_step=after_step)


DRIVES = pytest.mark.parametrize(
    "drive", [drive_full, InferenceEngine.generate],
    ids=["caller_fed", "engine_continued"])


# --------------------------------------------------------------------------
# proposer units (pure host-side, no device work)
# --------------------------------------------------------------------------

class TestNgramProposer:
    def test_basic_lookup(self):
        p = NgramProposer(max_draft=3)
        p.observe(1, [10, 11, 12, 13, 10, 11])
        # suffix [10, 11] last occurred at positions 0..1 -> followed
        # by [12, 13, 10]
        assert p.propose(1, 11, 3) == [12, 13, 10]

    def test_cyclic_extension(self):
        """A short cycle drafts at full width by wrapping the period —
        the attractor greedy decoding of small models falls into."""
        p = NgramProposer(max_draft=6)
        p.observe(1, [7, 7, 7])
        assert p.propose(1, 7, 6) == [7] * 6

    def test_limit_and_max_draft_cap(self):
        p = NgramProposer(max_draft=2)
        p.observe(1, [1, 2, 3, 1, 2])
        assert p.propose(1, 2, 5) == [3, 1]     # max_draft caps
        assert p.propose(1, 2, 1) == [3]        # limit caps
        assert p.propose(1, 2, 0) == []

    def test_no_match_degrades_to_empty(self):
        p = NgramProposer(max_draft=4)
        p.observe(1, [1, 2, 3, 4, 5])
        assert p.propose(1, 5, 4) == []

    def test_longest_ngram_wins(self):
        """[1,2,9] recurs and [2,9] also occurs after a different
        continuation; the 3-gram match must win over shorter ones."""
        p = NgramProposer(max_draft=2, max_ngram=3)
        p.observe(1, [1, 2, 9, 50, 60, 2, 9, 70, 1, 2, 9])
        assert p.propose(1, 9, 2) == [50, 60]

    def test_feedback_sentinel_skipped(self):
        p = NgramProposer(max_draft=3)
        p.observe(1, [1, 2, -7, 1, 2])          # -7: marker, not content
        assert p.history_len(1) == 4
        assert p.propose(1, 2, 3)[0] == -7 or True  # no crash suffices
        # the history holds [1, 2, 1, 2]; suffix [1, 2] recurred
        assert p.propose(1, 2, 2) == [1, 2]

    def test_heal_on_unseen_tail(self):
        """Direct-API callers may feed tokens the engine never emitted
        (teacher forcing); the history self-heals so the match anchors
        at the true fed token."""
        p = NgramProposer(max_draft=2)
        p.observe(1, [4, 5, 6, 4])
        assert p.propose(1, 5, 2) == [6, 4]     # healed: ...4, 5
        assert p.history_len(1) == 5

    def test_forget(self):
        p = NgramProposer(max_draft=2)
        p.observe(1, [1, 2, 1, 2])
        p.forget(1)
        assert p.history_len(1) == 0
        assert p.propose(1, 2, 2) == []

    def test_validation(self):
        with pytest.raises(ValueError, match="max_draft"):
            NgramProposer(0)
        with pytest.raises(ValueError, match="min_ngram"):
            NgramProposer(2, max_ngram=1, min_ngram=3)


# --------------------------------------------------------------------------
# StateManager: draft windows + write-cursor rollback
# --------------------------------------------------------------------------

class TestResolveDraft:
    def cfg(self):
        return KVCacheConfig(num_layers=2, num_kv_heads=2, head_dim=16,
                             block_size=4, num_blocks=16)

    def test_window_metadata(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        b = sm.build_batch([(0, [1, 2, 3, 4, 5])], token_budget=8,
                           n_verify=3)
        # no draft: column 0 is the legacy logits_idx, rest padded
        s = sm.slot(0)
        vi = np.asarray(b.verify_idx)
        assert vi.shape[1] == 3
        assert vi[s, 0] == int(b.logits_idx[s]) and list(vi[s, 1:]) == [-1, -1]
        sm.build_batch([(0, [9])], token_budget=8, n_verify=3)
        b = sm.build_batch([(0, [10, 61, 62])], token_budget=8,
                           draft_lens={0: 2}, n_verify=3)
        vi = np.asarray(b.verify_idx)
        # window spans the trailing 3 tokens (fed + 2 drafts)
        assert list(vi[s]) == [0, 1, 2]
        assert sm.seqs[0].draft_len == 2

    def test_rollback_truncates_cursor_and_chain(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        sm.build_batch([(0, [1, 2, 3])], token_budget=8)
        sm.build_batch([(0, [4, 61, 62, 63])], token_budget=8,
                       draft_lens={0: 3}, n_verify=4)
        seq = sm.seqs[0]
        assert seq.seen_tokens == 7 and seq.draft_len == 3
        rejected = sm.resolve_draft(0, accepted=1)
        assert rejected == 2
        assert seq.seen_tokens == 5 and seq.draft_len == 0
        assert seq.chain == [1, 2, 3, 4, 61]
        # idempotent: a second resolve is a no-op
        assert sm.resolve_draft(0, accepted=1) == 0
        assert seq.seen_tokens == 5

    def test_full_accept_keeps_everything(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        sm.build_batch([(0, [1, 2, 61, 62])], token_budget=8,
                       draft_lens={0: 2}, n_verify=3)
        assert sm.resolve_draft(0, accepted=2) == 0
        assert sm.seqs[0].seen_tokens == 4
        assert sm.seqs[0].chain == [1, 2, 61, 62]

    def test_unresolved_draft_blocks_next_schedule(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        sm.build_batch([(0, [1, 61])], token_budget=8,
                       draft_lens={0: 1}, n_verify=2)
        with pytest.raises(ValueError, match="unresolved draft"):
            sm.build_batch([(0, [5])], token_budget=8, n_verify=2)

    def test_draft_needs_wide_enough_window(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        with pytest.raises(ValueError, match="window"):
            sm.build_batch([(0, [1, 61, 62])], token_budget=8,
                           draft_lens={0: 2}, n_verify=2)

    def test_rollback_respects_refcounted_blocks(self):
        """Draft-pending blocks are never registered in the prefix
        cache, so rollback cannot corrupt a shared block; registration
        happens post-resolve with only committed content."""
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        sm.build_batch([(0, [1, 2, 3, 4, 61, 62])], token_budget=8,
                       draft_lens={0: 2}, n_verify=3)
        # the full block [1,2,3,4] is complete but holds no drafts; the
        # second block's drafts are provisional -> nothing registered yet
        assert not sm._hash_index
        sm.resolve_draft(0, accepted=0)
        # post-resolve, the committed full block registers
        assert len(sm._hash_index) == 1
        sm.allocator.assert_invariants()


# --------------------------------------------------------------------------
# config gating
# --------------------------------------------------------------------------

class TestConfigGating:
    def test_invalid_mode_raises(self, model):
        with pytest.raises(ValueError, match="spec_decode"):
            mk(model, spec_decode="maybe")

    def test_auto_resolves_off_today(self, model):
        """'auto' is the autotuner seam (ROADMAP item 4): until measured
        acceptance profiles drive it, it must resolve off so the
        compiled step stays byte-identical to a pre-spec engine."""
        eng = mk(model, spec_decode="auto")
        assert eng._spec is None and eng._n_verify == 1

    def test_bad_max_draft_raises(self, model):
        with pytest.raises(ValueError, match="spec_max_draft"):
            mk(model, spec_decode="on", spec_max_draft=0)

    def test_on_enables(self, model):
        eng = mk(model, spec_decode="on", spec_max_draft=3)
        assert eng._spec is not None and eng._n_verify == 4

    def test_weight_stream_forces_spec_off(self, tmp_path):
        """Under ``weight_stream`` speculative windows are forced off
        with one warning, and the engine really is draft-free (its
        compiled step is the legacy single-sample program)."""
        import logging

        m = build_model("llama-tiny", vocab_size=128, num_layers=3,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                        max_seq_len=64)
        records = []

        class _Tap(logging.Handler):
            def emit(self, record):
                records.append(record)

        lg = logging.getLogger("deepspeed_tpu")   # propagate=False: tap it
        tap = _Tap(level=logging.WARNING)
        lg.addHandler(tap)
        try:
            eng = InferenceEngine(m, InferenceConfig(
                token_budget=16, max_seqs=2, kv_block_size=8,
                num_kv_blocks=32, attn_impl="xla",
                weight_stream=str(tmp_path / "w"),
                spec_decode="on", spec_max_draft=2))
        finally:
            lg.removeHandler(tap)
        assert eng.icfg.spec_decode == "off"
        assert eng._spec is None and eng._n_verify == 1
        warns = [r for r in records
                 if "resident weights" in r.getMessage()]
        assert len(warns) == 1 and "spec_decode" in warns[0].getMessage()
        # the default config stays NOISE-FREE: "auto" resolves off on
        # its own, so a weight_stream engine with default spec settings
        # must not warn about forcing anything
        records.clear()
        lg.addHandler(tap)
        try:
            eng2 = InferenceEngine(m, InferenceConfig(
                token_budget=16, max_seqs=2, kv_block_size=8,
                num_kv_blocks=32, attn_impl="xla",
                weight_stream=str(tmp_path / "w2")))
        finally:
            lg.removeHandler(tap)
        assert eng2._spec is None and eng2._n_verify == 1
        assert not [r for r in records
                    if "resident weights" in r.getMessage()]
        # streamed decode still works, draft-free
        eng.put(1, [5, 17, 99])
        for _ in range(6):
            outs = eng.step()
            if 1 in outs:
                eng.put(1, [outs[1]])
        assert len(eng.query(1)["generated"]) >= 1
        assert eng.timings["spec_windows"] == 0


# --------------------------------------------------------------------------
# the exact-parity bar
# --------------------------------------------------------------------------

class TestSpecParity:
    """Outputs must be token-identical with spec_decode on vs off — the
    draft source may only change HOW FAST tokens arrive — whether the
    caller feeds each token (a window is cut by the caller) or the
    engine continues the request (``generate()``: a window is capped by
    what the request may still emit)."""

    @DRIVES
    @pytest.mark.parametrize("cache", ["on", "off"])
    def test_greedy_parity(self, model, drive, cache):
        sp = SamplingParams(max_new_tokens=24)
        ref = drive_full(mk(model, spec_decode="off", prefix_cache=cache),
                         MIXED, sp)
        eng = mk(model, spec_decode="on", spec_max_draft=4,
                 prefix_cache=cache)
        got = drive(eng, {u: list(p) for u, p in MIXED.items()}, sp)
        assert got == ref
        # the repetitive stream actually speculated (cycle attractor)
        assert eng.timings["spec_drafted_tokens"] > 0
        # full roll-up: no leaked draft state, allocator partition holds
        assert not eng.state.seqs and not eng.state._slots
        eng.state.allocator.assert_invariants()

    @DRIVES
    @pytest.mark.parametrize("cache", ["on", "off"])
    def test_seeded_parity(self, model, drive, cache):
        sp = SamplingParams(temperature=1.0, top_k=8, max_new_tokens=16)
        outs = {}
        for spec in ("off", "on"):
            eng = mk(model, spec_decode=spec, spec_max_draft=4,
                     prefix_cache=cache)
            outs[spec] = drive(
                eng, {u: list(p) for u, p in MIXED.items()}, sp,
                rng=jax.random.PRNGKey(7))
        assert outs["on"] == outs["off"]

    def test_stop_token_inside_accepted_draft(self, model):
        """A stop token covered by an accepted draft window must
        truncate the emission exactly where the stepwise engine stops
        feeding — nothing after the stop leaks out."""
        sp = SamplingParams(max_new_tokens=32)
        ref = mk(model, spec_decode="off").generate(
            {1: list(REPETITIVE)}, sp)[1]
        # stop on the token whose FIRST occurrence is deepest in the
        # stream: by then the cycle-following windows are accepting, so
        # the stop lands inside (or right at the edge of) a live window
        first = {}
        for i, t in enumerate(ref):
            first.setdefault(t, i)
        stop = max(first, key=first.get)
        sps = SamplingParams(max_new_tokens=32, stop_token=stop)
        want = ref[:ref.index(stop) + 1]
        for drive in (drive_full, InferenceEngine.generate):
            eng = mk(model, spec_decode="on", spec_max_draft=4)
            got = drive(eng, {1: list(REPETITIVE)}, sps)[1]
            assert got == want, drive.__name__
            assert eng.timings["spec_accepted_tokens"] > 0

    def test_preemption_parity(self, model):
        """Preempt-then-resume with spec on is token-identical to the
        undisturbed non-speculative run (greedy and seeded)."""
        prompts = {0: list(REPETITIVE), 1: [9, 2, 9, 2, 9, 2, 44]}
        kw = dict(num_kv_blocks=16, prefix_cache="on")
        sp = SamplingParams(max_new_tokens=8)
        ref = drive_full(mk(model, spec_decode="off", **kw),
                         dict(prompts), sp)
        eng = mk(model, spec_decode="on", spec_max_draft=4, **kw)
        got = drive_full(eng, dict(prompts), sp, preempt=(0, 3))
        assert got == ref
        assert eng.request_metrics()["aggregate"]["preemptions"] == 1
        eng.state.allocator.assert_invariants()

    def test_preemption_parity_seeded_cache_off(self, model):
        prompts = {0: list(REPETITIVE), 1: [9, 2, 9, 2, 9, 2, 44]}
        kw = dict(num_kv_blocks=16, prefix_cache="off")
        sp = SamplingParams(temperature=1.0, top_k=8, max_new_tokens=8)
        rng = jax.random.PRNGKey(17)
        ref = drive_full(mk(model, spec_decode="off", **kw),
                         dict(prompts), sp, rng=rng)
        got = drive_full(mk(model, spec_decode="on", spec_max_draft=4,
                            **kw), dict(prompts), sp, rng=rng,
                         preempt=(1, 3))
        assert got == ref

    def test_chunked_prefill_parity(self, model):
        """Drafts compete with prefill chunks for the same SplitFuse
        budget (`prefill_chunk` caps prompts per step, decode packs
        first, drafts ride the decode class) — mixed chunked traffic
        stays token-identical with spec on."""
        from deepspeed_tpu.inference.overload import OverloadConfig

        r = np.random.RandomState(3)
        prompts = {0: list(REPETITIVE), 1: list(r.randint(1, 128, 40)),
                   2: [9, 2] * 8}
        sp = SamplingParams(max_new_tokens=12)
        outs = {}
        for spec in ("off", "on"):
            eng = mk(model, token_budget=16, kv_block_size=8,
                     spec_decode=spec, spec_max_draft=4,
                     overload=OverloadConfig(prefill_chunk=6))
            outs[spec] = eng.generate(
                {u: list(p) for u, p in prompts.items()}, sp)
        assert outs["on"] == outs["off"]

    def test_step_api_returns_continuation_token(self, model):
        """Direct step() callers get the LAST window token — the right
        continuation to feed back — while the full stream accumulates on
        the sequence (query())."""
        eng = mk(model, spec_decode="on", spec_max_draft=4)
        eng.put(1, list(REPETITIVE))
        got = []
        for _ in range(12):
            outs = eng.step()
            if 1 in outs:
                got.append(outs[1])
                eng.put(1, [outs[1]])
            q = eng.query(1)
            assert q["generated"] == eng.state.seqs[1].tokens
        full = eng.query(1)["generated"]
        # every step() return is the tail of the stream at that point
        assert got[-1] == full[-1]
        assert eng.timings["spec_accepted_tokens"] > 0


# --------------------------------------------------------------------------
# speedup + telemetry accounting
# --------------------------------------------------------------------------

class TestSpecAccounting:
    def test_fewer_steps_on_repetitive_stream(self, model):
        """The perf claim at its smallest: the cycle-following stream
        needs strictly fewer dispatched steps with spec on."""
        sp = SamplingParams(max_new_tokens=32)
        steps = {}
        for spec in ("off", "on"):
            eng = mk(model, spec_decode=spec, spec_max_draft=4)
            eng.generate({1: list(REPETITIVE)}, sp)
            steps[spec] = eng.timings["steps"]
        assert steps["on"] < steps["off"]

    def test_counters_reconcile(self, model):
        """drafted == accepted + rejected, sum(per-request) == engine
        counter for the new counters AND the existing generated_tokens
        invariant — same statements, by construction."""
        eng = mk(model, spec_decode="on", spec_max_draft=4)
        sp = SamplingParams(max_new_tokens=16)
        out = eng.generate({u: list(p) for u, p in MIXED.items()}, sp)
        tm = eng.timings
        assert tm["spec_drafted_tokens"] > 0
        assert tm["spec_drafted_tokens"] == tm["spec_accepted_tokens"] \
            + tm["spec_rejected_tokens"]
        assert tm["spec_windows"] > 0
        rm = eng.request_metrics()
        recs = rm["requests"]
        assert sum(r["drafted_tokens"] for r in recs) \
            == tm["spec_drafted_tokens"]
        assert sum(r["accepted_tokens"] for r in recs) \
            == tm["spec_accepted_tokens"]
        assert sum(r["generated_tokens"] for r in recs) \
            == tm["generated_tokens"] == sum(len(v) for v in out.values())
        agg = rm["aggregate"]
        assert agg["drafted_tokens"] == tm["spec_drafted_tokens"]
        assert agg["accepted_tokens"] == tm["spec_accepted_tokens"]
        assert agg["acceptance_rate"] == pytest.approx(
            tm["spec_accepted_tokens"] / tm["spec_drafted_tokens"],
            abs=1e-3)
        # per-request acceptance_rate exposed for the autotuner
        drafted = [r for r in recs if r["drafted_tokens"]]
        assert drafted and all(0.0 <= r["acceptance_rate"] <= 1.0
                               for r in drafted)

    def test_counters_silent_when_off(self, model):
        eng = mk(model, spec_decode="off")
        eng.generate({1: list(REPETITIVE)},
                     SamplingParams(max_new_tokens=8))
        tm = eng.timings
        assert tm["spec_drafted_tokens"] == 0 and tm["spec_windows"] == 0
        rm = eng.request_metrics()
        assert rm["aggregate"]["acceptance_rate"] is None
        assert all(r["acceptance_rate"] is None for r in rm["requests"])

    def test_reset_metrics_clears_spec_counters(self, model):
        eng = mk(model, spec_decode="on", spec_max_draft=4)
        eng.generate({1: list(REPETITIVE)},
                     SamplingParams(max_new_tokens=16))
        assert eng.timings["spec_drafted_tokens"] > 0
        eng.reset_metrics()
        assert eng.timings["spec_drafted_tokens"] == 0
        agg = eng.request_metrics()["aggregate"]
        assert agg["drafted_tokens"] == 0
        assert agg["acceptance_rate"] is None
