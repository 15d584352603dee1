"""Inference/ragged-batching tests (reference analogs:
tests/unit/inference/v2/ragged/test_blocked_allocator.py,
test_ragged_wrapper.py; engine-level scheduling tests; decode parity
with the dense forward)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (BlockedAllocator, InferenceConfig,
                                     InferenceEngine, SamplingParams,
                                     StateManager, KVCacheConfig)
from deepspeed_tpu.inference.sampler import sample
from deepspeed_tpu.models import apply, build_model
from tests.serving_ref import strict_generate


def tiny_model(**over):
    kw = dict(vocab_size=128, num_layers=2, d_model=64, num_heads=4,
              num_kv_heads=2, d_ff=128, max_seq_len=128)
    kw.update(over)
    return build_model("llama-tiny", **kw)


def make_engine(m, **over):
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=16,
              num_kv_blocks=64)
    kw.update(over)
    return InferenceEngine(m, InferenceConfig(**kw))


@contextlib.contextmanager
def backend_compiles():
    """The XLA backend compiles made inside the block, as a list that
    fills while it runs (``jax.monitoring``'s compile-duration event)."""
    from jax import monitoring
    from jax._src.monitoring import unregister_event_duration_listener

    compiles = []

    def on_duration(name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield compiles
    finally:
        unregister_event_duration_listener(on_duration)


def make_fp32_engine(m, **over):
    """fp32 engine for exact-parity tests (bf16 argmax near-ties are
    legitimately order-sensitive)."""
    return make_engine(m, kv_dtype=jnp.float32, param_dtype=jnp.float32,
                       **over)


class TestBlockedAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(5)
        assert len(blocks) == 5 and a.free_blocks == 3
        a.free(blocks[:2])
        assert a.free_blocks == 5

    def test_over_allocate_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError, match="Cannot allocate"):
            a.allocate(5)

    def test_double_free_raises(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free(b)
        with pytest.raises(ValueError, match="Double free"):
            a.free([b[0]])

    def test_invalid_block_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError, match="Invalid block"):
            a.free([99])


class TestStateManager:
    def cfg(self):
        return KVCacheConfig(num_layers=2, num_kv_heads=2, head_dim=16,
                             block_size=4, num_blocks=16)

    def test_sequence_lifecycle(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        sm.build_batch([(0, [1, 2, 3, 4, 5])], token_budget=8)
        assert sm.seqs[0].seen_tokens == 5
        assert len(sm.seqs[0].blocks) == 2          # ceil(5/4)
        free_before = sm.allocator.free_blocks
        sm.release(0)
        assert sm.allocator.free_blocks == free_before + 2
        assert 0 not in sm.seqs

    def test_can_schedule_respects_blocks(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        assert sm.can_schedule(0, 16 * 4)
        assert not sm.can_schedule(0, 16 * 4 + 1)

    def test_slot_exhaustion(self):
        sm = StateManager(self.cfg(), max_seqs=1)
        sm.build_batch([(0, [1])], token_budget=4)
        assert not sm.can_schedule(1, 1)

    def test_batch_metadata(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        b = sm.build_batch([(0, [1, 2, 3]), (1, [7])], token_budget=8)
        assert b.n_tokens == 4 and b.n_seqs == 2
        np.testing.assert_array_equal(np.asarray(b.positions[:4]),
                                      [0, 1, 2, 0])
        assert int(b.logits_idx[sm.slot(0)]) == 2
        assert int(b.logits_idx[sm.slot(1)]) == 3
        assert not bool(b.token_valid[4])

    def test_budget_overflow_raises(self):
        sm = StateManager(self.cfg(), max_seqs=2)
        with pytest.raises(ValueError, match="budget"):
            sm.build_batch([(0, list(range(9)))], token_budget=8)


class TestDecodeParity:
    def test_greedy_matches_full_forward(self):
        m = tiny_model()
        eng = make_fp32_engine(m)
        prompt = [5, 17, 99, 3, 42]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=8))
        params = m.params
        seq = list(prompt)
        for _ in range(8):
            logits = apply(m.config, params, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert out[0] == seq[len(prompt):]

    def test_gpt2_style_learned_positions(self):
        m = build_model("gpt2", vocab_size=128, num_layers=2, d_model=64,
                        num_heads=4, max_seq_len=64)
        eng = make_fp32_engine(m)
        prompt = [1, 2, 3]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=5))
        params = m.params
        seq = list(prompt)
        for _ in range(5):
            logits = apply(m.config, params, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert out[0] == seq[len(prompt):]

    def test_continuous_batching_isolation(self):
        """Interleaved sequences decode identically to solo runs."""
        m = tiny_model()
        eng = make_engine(m)
        out = eng.generate({1: [3, 1, 4], 2: [2, 7, 1, 8, 2, 8]},
                           SamplingParams(max_new_tokens=5))
        for uid, p in ((1, [3, 1, 4]), (2, [2, 7, 1, 8, 2, 8])):
            solo = make_engine(m).generate({uid: p},
                                           SamplingParams(max_new_tokens=5))
            assert solo[uid] == out[uid]

    def test_splitfuse_chunked_prefill(self):
        """Prompt longer than the budget is ingested over several steps
        and still decodes identically (Dynamic SplitFuse)."""
        m = tiny_model()
        prompt = list(np.random.RandomState(0).randint(1, 128, 50))
        small = make_engine(m, token_budget=16)
        big = make_engine(m, token_budget=64)
        a = small.generate({0: prompt}, SamplingParams(max_new_tokens=4))
        b = big.generate({0: prompt}, SamplingParams(max_new_tokens=4))
        assert a[0] == b[0]

    def test_moe_decode(self):
        m = build_model("mixtral-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        num_experts=4, capacity_factor=4.0)
        eng = make_engine(m)
        out = eng.generate({0: [1, 2, 3]}, SamplingParams(max_new_tokens=4))
        assert len(out[0]) == 4


class TestEngineAPI:
    def test_query_flush(self):
        m = tiny_model()
        eng = make_engine(m)
        eng.put(7, [1, 2, 3])
        assert eng.query(7)["pending_tokens"] == 3
        eng.step()
        q = eng.query(7)
        assert q["seen_tokens"] == 3
        eng.flush(7)
        assert eng.query(7)["seen_tokens"] == 0

    def test_stop_token(self):
        m = tiny_model()
        prompt = [5, 17, 99, 3, 42]
        # derive the model's first greedy token (hardcoding it ties the
        # test to one XLA version's float scheduling), then use it as the
        # stop token: generation must end immediately with just that token
        first = make_fp32_engine(m).generate(
            {0: list(prompt)},
            SamplingParams(temperature=0.0, max_new_tokens=1))[0][0]
        eng = make_fp32_engine(m)
        out = eng.generate({0: list(prompt)},
                           SamplingParams(max_new_tokens=50,
                                          stop_token=first))
        assert out[0] == [first]


class TestSampler:
    def test_greedy(self):
        logits = jnp.asarray([[0.0, 3.0, 1.0], [2.0, 0.0, 0.0]])
        toks = sample(logits, SamplingParams(temperature=0.0))
        np.testing.assert_array_equal(np.asarray(toks), [1, 0])

    def test_top_k_restricts(self):
        logits = jnp.asarray([[0.0, 10.0, 9.0, -5.0]])
        rng = jax.random.PRNGKey(0)
        for i in range(10):
            t = sample(logits, SamplingParams(temperature=1.0, top_k=2),
                       jax.random.fold_in(rng, i))
            assert int(t[0]) in (1, 2)

    def test_top_p_restricts(self):
        logits = jnp.asarray([[10.0, 9.5, -20.0, -20.0]])
        rng = jax.random.PRNGKey(0)
        for i in range(10):
            t = sample(logits, SamplingParams(temperature=1.0, top_p=0.9),
                       jax.random.fold_in(rng, i))
            assert int(t[0]) in (0, 1)


class TestSchedulerSafety:
    def test_overcommit_blocks_no_crash(self):
        """Two prompts that jointly exceed the KV pool must be admitted
        incrementally, not crash build_batch mid-step."""
        m = tiny_model()
        eng = make_engine(m, num_kv_blocks=4, kv_block_size=16,
                          token_budget=128, max_seqs=4)
        p1 = list(np.random.RandomState(1).randint(1, 128, 33))
        p2 = list(np.random.RandomState(2).randint(1, 128, 33))
        eng.put(0, p1)
        eng.put(1, p2)
        for _ in range(10):
            eng.step()
        # both prompts eventually fully ingested or bounded by pool
        assert eng.query(0)["seen_tokens"] + eng.query(1)["seen_tokens"] <= 64

    def test_slot_overcommit_no_crash(self):
        m = tiny_model()
        eng = make_engine(m, max_seqs=1)
        eng.put(0, [1, 2])
        eng.put(1, [3, 4])
        eng.step()
        assert eng.query(0)["seen_tokens"] == 2
        assert eng.query(1)["seen_tokens"] == 0   # deferred, not crashed
        eng.flush(0)
        eng.step()
        assert eng.query(1)["seen_tokens"] == 2

    def test_context_limit_ends_generation(self):
        m = tiny_model()
        # 2 blocks x 16 = 32-token max context
        eng = make_engine(m, num_kv_blocks=2, kv_block_size=16,
                          max_seqs=1, max_seq_len=32)
        out = eng.generate({0: [1, 2, 3, 4]},
                           SamplingParams(max_new_tokens=100))
        # last token is sampled when seen==32; generation then stops:
        # 4 prompt + 28 fed-back tokens ingested -> 29 sampled
        assert len(out[0]) == 29

    def test_decode_prioritized_over_prefill(self):
        """A decoding sequence is not starved by a long new prompt."""
        m = tiny_model()
        eng = make_engine(m, token_budget=8)
        eng.put(0, [1, 2, 3])
        eng.step()                      # seq 0 ready to decode
        eng.put(0, [42])                # decode token
        eng.put(1, list(range(1, 30)))  # long prefill
        eng.step()
        assert eng.query(0)["seen_tokens"] == 4   # decode went through


class TestPipelinedServing:
    """``generate()`` runs on the step that runs ahead (on-device
    sampling + deferred token feedback + double-buffered staging) and
    must be token-for-token identical to the strict caller-fed loop —
    both run the same step computation; only dispatch/readback cadence
    differs."""

    PROMPTS = {0: [5, 17, 99, 3, 42], 1: [7, 7, 1]}

    @staticmethod
    def _gen(eng, prompts, sp, rng=None):
        return eng.generate({u: list(p) for u, p in prompts.items()},
                            sp, rng=rng)

    def test_ahead_matches_strict(self):
        """Greedy, stop-token, and seeded-sampling parity on one engine
        pair (both loops flush everything, so the engines are reused
        across phases — and greedy/stop share one compiled step)."""
        m = tiny_model()
        e1 = make_fp32_engine(m)
        e2 = make_fp32_engine(m)
        sp = SamplingParams(max_new_tokens=10)
        sync = strict_generate(e1, self.PROMPTS, sp)
        piped = self._gen(e2, self.PROMPTS, sp)
        assert piped == sync
        # stop token mid-stream: the served loop has one step launched
        # ahead when it fires; its token must be discarded
        sps = SamplingParams(max_new_tokens=50, stop_token=sync[0][3])
        one = {0: self.PROMPTS[0]}
        got = self._gen(e2, one, sps)
        assert got == strict_generate(e1, one, sps)
        assert got[0][-1] == sync[0][3]
        # fixed-rng sampling: a token's key is its (uid, position) fold
        spr = SamplingParams(temperature=1.0, top_k=8, max_new_tokens=8)
        assert self._gen(e2, self.PROMPTS, spr,
                         rng=jax.random.PRNGKey(7)) \
            == strict_generate(e1, self.PROMPTS, spr,
                               rng=jax.random.PRNGKey(7))
        # no leaked feedback markers, sequences, slots, or blocks after
        # the runs ahead (speculation fully rolled up), nothing in flight
        assert e2._fb_step == {} and not e2.in_flight
        assert not e2.state.seqs and not e2.state._slots
        assert e2.state.allocator.free_blocks \
            == e2.state.allocator.total_blocks
        # per-phase breakdown recorded
        t = e2.timings
        assert t["steps"] > 0
        assert all(t[k] >= 0.0 for k in ("schedule_ms", "stage_ms",
                                         "device_ms", "wait_ms",
                                         "readback_ms"))

    def test_mixed_prefill_decode_traffic(self):
        """Prompts straddling the token budget: chunked prefill, decode,
        and prefill+decode mixed steps all run ahead identically."""
        m = tiny_model()
        r = np.random.RandomState(3)
        prompts = {0: list(r.randint(1, 128, 50)), 1: [3, 1, 4],
                   2: list(r.randint(1, 128, 20))}
        sp = SamplingParams(max_new_tokens=6)
        sync = strict_generate(make_fp32_engine(m, token_budget=16),
                               prompts, sp)
        piped = self._gen(make_fp32_engine(m, token_budget=16), prompts, sp)
        assert piped == sync

    def test_budget_starvation(self):
        """A budget smaller than the live decode count: a decode whose
        continuation was launched ahead may wait a step for its turn,
        and by then its marker has been patched concrete by the read of
        the launch that owned it (feeding it another step's sample
        array would be silently wrong, not an error)."""
        m = tiny_model()
        prompts = {0: [5, 9], 1: [7, 7], 2: [3, 1], 3: [8, 2]}
        sp = SamplingParams(max_new_tokens=5)
        sync = strict_generate(make_fp32_engine(m, token_budget=2),
                               prompts, sp)
        eng = make_fp32_engine(m, token_budget=2)
        assert self._gen(eng, prompts, sp) == sync
        assert eng.metrics_snapshot()["serving_steps_ahead_total"] > 0

    def test_context_limit(self):
        """A sequence ending at the context limit still emits its final
        in-flight token before the engine closes it."""
        m = tiny_model()
        eng = make_fp32_engine(m, num_kv_blocks=2, kv_block_size=16,
                               max_seqs=1, max_seq_len=32)
        out = eng.generate({0: [1, 2, 3, 4]},
                           SamplingParams(max_new_tokens=100))
        assert len(out[0]) == 29        # same bound as the strict loop
        assert eng.query(0)["status"] == "context_exhausted"


class TestChunkedPagedAttention:
    def test_chunked_matches_one_shot(self, monkeypatch):
        """Past the gather-bytes cap the XLA path streams one KV block at
        a time (online softmax); greedy decode must match the one-shot
        gather exactly (fix for an HBM OOM at the bench's GPT-2 shapes)."""
        from deepspeed_tpu.inference import model as im

        m = tiny_model()
        prompt = {0: [5, 17, 99, 3, 42, 7], 1: [11, 2]}
        sp = SamplingParams(temperature=0.0, max_new_tokens=6)
        ref = make_fp32_engine(m, attn_impl="xla").generate(
            {u: list(p) for u, p in prompt.items()}, sp)
        monkeypatch.setattr(im, "_ONE_SHOT_GATHER_BYTES", 0)
        chunked = make_fp32_engine(m, attn_impl="xla").generate(
            {u: list(p) for u, p in prompt.items()}, sp)
        assert ref == chunked


class TestNewFamilyServing:
    @pytest.mark.parametrize("preset,over", [
        ("qwen2-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                            num_heads=4, num_kv_heads=2, d_ff=128,
                            max_seq_len=64)),
        ("gptj-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                           num_heads=4, max_seq_len=64)),
        ("gpt-neox-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                               num_heads=4, max_seq_len=64)),
        ("phi3-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                           num_heads=4, d_ff=128, max_seq_len=64)),
        ("internlm-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                               num_heads=4, d_ff=128, max_seq_len=64)),
        ("gpt-neo-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                              num_heads=4, max_seq_len=64)),
        ("qwen2-moe-tiny", dict(vocab_size=128, num_layers=2, d_model=64,
                                num_heads=4, num_kv_heads=2, d_ff=96,
                                moe_shared_ff=160, num_experts=4,
                                max_seq_len=64, capacity_factor=4.0)),
    ])
    def test_greedy_matches_full_forward(self, preset, over):
        m = build_model(preset, **over)
        eng = make_fp32_engine(m)
        prompt = [5, 17, 99, 3]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=6))
        seq = list(prompt)
        for _ in range(6):
            logits = apply(m.config, m.params, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert out[0] == seq[len(prompt):]


class TestAlibiServing:
    """ALiBi (BLOOM-class) serving parity: all paged-attention paths
    carry the additive slope*key-position bias (reference analog: the
    alibi operand of csrc/transformer/inference/csrc/softmax.cu)."""

    def _model(self, **over):
        kw = dict(vocab_size=128, num_layers=2, d_model=64, num_heads=4,
                  max_seq_len=128)
        kw.update(over)
        return build_model("bloom-tiny", **kw)

    def _eval_tokens(self, m, prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = apply(m.config, m.params, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    def test_greedy_matches_eval(self):
        m = self._model()
        eng = make_fp32_engine(m)
        prompt = [5, 17, 99, 3, 42]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=8))
        assert out[0] == self._eval_tokens(m, prompt, 8)

    def test_chunked_path_matches_eval(self, monkeypatch):
        from deepspeed_tpu.inference import model as im
        monkeypatch.setattr(im, "_ONE_SHOT_GATHER_BYTES", 0)
        m = self._model()
        eng = make_fp32_engine(m)
        prompt = [9, 2, 77]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=6))
        assert out[0] == self._eval_tokens(m, prompt, 6)

    def test_pallas_impl_matches_eval(self):
        m = self._model()
        eng = make_fp32_engine(m, attn_impl="pallas")
        prompt = [5, 17, 99, 3]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=6))
        assert out[0] == self._eval_tokens(m, prompt, 6)

    def test_gqa_alibi_slopes_per_group(self):
        """GQA + ALiBi: slopes index full head ids (h = hkv*rep + r)."""
        m = self._model(num_heads=4, num_kv_heads=2)
        eng = make_fp32_engine(m)
        prompt = [8, 6, 7, 5]
        out = eng.generate({0: prompt}, SamplingParams(max_new_tokens=6))
        assert out[0] == self._eval_tokens(m, prompt, 6)


class TestQuantizedKV:
    """int8/fp8 paged KV cache with per-vector scales (reference analog:
    ZeRO-Inference KV quantization, deepspeed/inference/quantization/).
    The step-mode consumers — one-shot gather, chunked online-softmax,
    Pallas kernel — read the same quantized cache, so their outputs must
    match each other EXACTLY."""

    PROMPT = [5, 17, 99, 3, 42]
    GR = SamplingParams(temperature=0.0, max_new_tokens=8)

    def _outs(self, m, **kw):
        eng = make_fp32_engine(m, **kw)
        return eng.generate({0: list(self.PROMPT)}, self.GR)[0]

    def test_cross_impl_exact(self, monkeypatch):
        m = tiny_model()
        xla = self._outs(m, kv_quant="int8", attn_impl="xla")
        pallas = self._outs(m, kv_quant="int8", attn_impl="pallas")
        from deepspeed_tpu.inference import model as im
        monkeypatch.setattr(im, "_ONE_SHOT_GATHER_BYTES", 0)
        chunked = self._outs(m, kv_quant="int8", attn_impl="xla")
        assert xla == pallas == chunked

    def test_close_to_fp_logits(self):
        """Per-vector int8 KV perturbs prefill logits by well under the
        greedy decision scale (deterministic check, no argmax ties)."""
        m = tiny_model()
        lg = {}
        for name, kw in (("fp", {}), ("q", {"kv_quant": "int8"})):
            eng = make_fp32_engine(m, **kw)
            eng.put(0, list(self.PROMPT))
            sched = eng._schedule()
            b = eng.state.build_batch(sched, eng.icfg.token_budget)
            out, _ = eng._build_step()(eng.params, eng._quant,
                                       eng.state.kv, b)
            lg[name] = np.asarray(out)[0]
        np.testing.assert_allclose(lg["q"], lg["fp"], atol=0.05, rtol=0.05)

    def test_fp8_runs_and_matches_xla(self):
        m = tiny_model()
        a = self._outs(m, kv_quant="fp8", attn_impl="xla")
        b = self._outs(m, kv_quant="fp8", attn_impl="pallas")
        assert a == b and len(a) == self.GR.max_new_tokens

    def test_quantized_cache_is_half_bytes(self):
        m = tiny_model()
        eng_fp = make_engine(m)                       # bf16 cache
        eng_q = make_engine(m, kv_quant="int8")
        fp_bytes = eng_fp.state.kv.size * eng_fp.state.kv.dtype.itemsize
        data, scales = eng_q.state.kv
        q_bytes = data.size * data.dtype.itemsize \
            + scales.size * scales.dtype.itemsize
        # 1 byte/elem + one f32 scale per D-vector (D=16 here)
        assert q_bytes < 0.7 * fp_bytes, (q_bytes, fp_bytes)

    def test_alibi_composes_with_kv_quant(self):
        m = build_model("bloom-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, max_seq_len=128)
        ref = self._outs(m)
        q = self._outs(m, kv_quant="int8")
        qp = self._outs(m, kv_quant="int8", attn_impl="pallas")
        assert q == qp == ref


class TestServingProgramRecord:
    """Each compiled serving program notes once, when it is compiled
    (ahead of its first call, with its step function's other row
    counts), what it needs beside its arguments
    (``engine.serving_programs``, one pull gauge) — read from the
    executable just built, which is the one its calls run: nothing
    compiles a second time."""

    @pytest.mark.parametrize("attn_impl,over", [
        ("xla", {}), ("xla", {"kv_quant": "int8"}),
        ("xla", {"kv_donate": "off"}), ("pallas", {}),
        ("xla", {"token_budget": 256})],
        ids=["bf", "int8kv", "no-donation", "pallas", "two-rungs"])
    def test_noted_once_without_a_second_compile(self, attn_impl, over):
        eng = make_fp32_engine(tiny_model(), attn_impl=attn_impl, **over)
        assert eng.serving_programs == {}
        assert "serving_step_temp_bytes" not in eng.metrics_snapshot()
        built, launches = [], []
        compile_rungs, dispatch = eng._compile_rungs, eng._dispatch

        def counted(key, *a):
            before = len(compiles)
            compile_rungs(key, *a)
            built.append((key, len(compiles) - before))

        def launch(*a, **k):
            before, n_built = len(compiles), sum(n for _, n in built)
            st = dispatch(*a, **k)
            launches.append(len(compiles) - before
                            - (sum(n for _, n in built) - n_built))
            return st

        eng._compile_rungs, eng._dispatch = counted, launch
        with backend_compiles() as compiles:
            sp = SamplingParams(temperature=0.0, max_new_tokens=20)
            out = eng.generate({0: list(range(1, 30)), 1: [5, 6, 7]}, sp)
        assert len(out[0]) == 20
        # one executable a row count of a step function, built with the
        # function, and no launch compiled anything beside them: under
        # an XLA formulation a step function a context bucket (16-token
        # blocks: 29 + 20 tokens reach the 2- and 4-block buckets); the
        # Pallas kernel's grid follows the batch, so there one function
        # bounded by the engine's longest context serves every step
        rungs = eng._step_rows
        assert len(rungs) == (2 if "token_budget" in over else 1)
        assert sorted(k for k, _ in built) == sorted(eng._pstep_fns)
        if attn_impl == "pallas":
            assert [k[0] for k, _ in built] == [eng.max_blocks_per_seq]
        else:
            assert len(built) >= 2
        assert all(n == len(rungs) for _, n in built)
        assert len(launches) >= 20 and not any(launches)
        assert sorted(eng.serving_programs) == sorted(
            (r,) + k for k in eng._pstep_fns for r in rungs)
        assert eng.timings["compiles"] == len(built) * len(rungs)
        for rec in eng.serving_programs.values():
            assert isinstance(rec["temp_bytes"], int)
        snap = eng.metrics_snapshot()
        assert snap["serving_step_temp_bytes"] == max(
            r["temp_bytes"] for r in eng.serving_programs.values())


class TestServingPathByRule:
    """Which attention formulation an engine runs, and whether its
    quantized projections go through the mixed-input kernel, is settled
    at construction by a rule over what the process can observe: nothing
    is compiled, run or timed for it, so two engines of one process can
    never disagree (a start-up race between the formulations once made
    two replicas of one model answer a bf16 near-tie differently)."""

    def test_auto_is_xla_off_the_chip(self):
        assert jax.default_backend() != "tpu"
        assert make_engine(tiny_model()).attn_impl == "xla"

    def test_auto_is_pallas_on_a_tpu_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert make_engine(tiny_model()).attn_impl == "pallas"

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_forced_value_is_kept(self, impl, monkeypatch):
        # on either side of the rule
        for backend in ("cpu", "tpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            eng = make_engine(tiny_model(), attn_impl=impl)
            assert eng.attn_impl == impl
            assert eng._resolve_fw(None)[0]["attn_impl"] == impl

    @pytest.mark.parametrize("over", [
        {"attn_impl": "flash"}, {"mixed_gemm": "auto"}],
        ids=["attn_impl", "mixed_gemm-auto"])
    def test_unknown_value_raises_at_construction(self, over):
        (name,) = over
        with pytest.raises(ValueError, match=name):
            make_engine(tiny_model(), weight_quant="int8", **over)

    def test_resolving_compiles_and_runs_nothing(self):
        eng = make_engine(tiny_model(), weight_quant="int8",
                          prefix_cache="on")
        kv = eng.state.kv
        eng.state.build_batch([(9, list(range(1, 33)))], 32)
        index = eng.state.prefix_digests()
        assert len(index) == 2     # the prompt's two full blocks
        with backend_compiles() as compiles:
            fw, mbs = eng._resolve_fw(None)
            eng._build_pstep(None, SamplingParams(temperature=0.0))
        assert fw["attn_impl"] == "xla" and fw["mixed_gemm"] is False
        assert mbs == eng.max_blocks_per_seq
        assert compiles == []
        assert eng.metrics_snapshot()["serving_compiles_total"] == 0
        assert eng.probe_times == {}
        # the cache and the prefix index are the ones construction and
        # the scheduler left
        assert eng.state.kv is kv
        assert eng.state.prefix_digests() == index

    def test_pool_size_does_not_change_the_answer(self):
        """tests/test_tier.py's fleet against its reference engine: two
        default-configured bf16 engines that differ only in
        ``num_kv_blocks`` (a replica's 16, the reference's 24) answer
        the trace's filler prompt of uid 3 token for token."""
        from tools.loadgen import build_engine

        prompt = [int(x) for x in
                  np.random.RandomState(602).randint(1, 120, 44)]
        sp = SamplingParams(temperature=0.0, max_new_tokens=4)
        small, model = build_engine(num_kv_blocks=16, prefix_cache="on")
        large, _ = build_engine(model=model, prefix_cache="on")
        assert small.icfg.attn_impl == large.icfg.attn_impl == "auto"
        assert small.attn_impl == large.attn_impl
        assert small.generate({3: prompt}, sp) == \
            large.generate({3: prompt}, sp)
