"""Automatic prefix caching for the paged KV cache (shared-prompt block
reuse with copy-on-write).

The correctness bar is STRICT parity: with greedy or seeded sampling,
``prefix_cache="on"`` must be token-for-token identical to ``"off"``
across mixed chunked traffic, stop tokens, pipeline depths 1 and 2, and
under eviction pressure (pool sized so cached blocks are reclaimed
mid-run) — plus allocator accounting
``referenced + cached_free + free == total`` after every phase, and the
hit-rate counters in ``engine.timings`` / ``query()`` asserted so the
metric cannot silently rot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                     KVCacheConfig, SamplingParams,
                                     StateManager)
from deepspeed_tpu.inference.ragged.allocator import BlockedAllocator
from deepspeed_tpu.models import build_model
from tests.serving_ref import strict_generate


@pytest.fixture(scope="module")
def model():
    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       max_seq_len=128)


def mk(m, **over):
    """fp32 engine (exact-parity convention of test_inference.py) with
    a block size small enough that 20-30-token prompts span blocks."""
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=8,
              num_kv_blocks=32, kv_dtype=jnp.float32,
              param_dtype=jnp.float32, prefix_cache="on")
    kw.update(over)
    return InferenceEngine(m, InferenceConfig(**kw))


def check_allocator(eng):
    al = eng.state.allocator
    al.assert_invariants()
    held = [b for s in eng.state.seqs.values() for b in s.blocks]
    assert al.free_blocks + len(set(held)) == al.total_blocks


GREEDY = SamplingParams(temperature=0.0, max_new_tokens=6)


class TestRefcountedAllocator:
    def test_alias_and_release_cycle(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(3)
        a.ref(blocks[0])                      # alias: refcount 2
        assert a.refcount(blocks[0]) == 2
        a.free(blocks)                        # drops one ref each
        assert a.refcount(blocks[0]) == 1     # still aliased
        assert a.free_blocks == 7
        a.free([blocks[0]])
        assert a.free_blocks == 8
        a.assert_invariants()

    def test_cached_free_lru_eviction_order(self):
        evicted = []
        a = BlockedAllocator(4, on_evict=evicted.append)
        blocks = a.allocate(4)
        for b in blocks:
            a.mark_cached(b)
        a.free([blocks[2]])                   # oldest on the LRU list
        a.free([blocks[0]])
        a.free([blocks[1]])
        assert a.cached_free_blocks == 3 and a.free_blocks == 3
        got = a.allocate(2)                   # evicts oldest-released
        assert evicted == [blocks[2], blocks[0]]
        assert got == [blocks[2], blocks[0]]
        a.assert_invariants()

    def test_revive_from_cached_free(self):
        a = BlockedAllocator(4)
        [b] = a.allocate(1)
        a.mark_cached(b)
        a.free([b])
        assert a.cached_free_blocks == 1
        a.ref(b)                              # match revives it
        assert a.refcount(b) == 1 and a.cached_free_blocks == 0
        a.free([b])
        a.assert_invariants()

    def test_free_list_preferred_over_cached(self):
        a = BlockedAllocator(4)
        [b] = a.allocate(1)
        a.mark_cached(b)
        a.free([b])
        got = a.allocate(3)
        assert b not in got                   # reuse-before-overwrite
        assert a.is_cached(b)
        a.assert_invariants()

    def test_double_free_and_bad_ref(self):
        a = BlockedAllocator(4)
        [b] = a.allocate(1)
        a.free([b])
        with pytest.raises(ValueError, match="Double free"):
            a.free([b])
        with pytest.raises(ValueError, match="Cannot ref"):
            a.ref(b)

    def test_duplicate_in_one_free_call_rejected_atomically(self):
        """More frees than references WITHIN one call must raise the
        documented ValueError and mutate nothing (not partially retire
        the block then KeyError)."""
        a = BlockedAllocator(4)
        [b] = a.allocate(1)
        with pytest.raises(ValueError, match="Double free"):
            a.free([b, b])
        assert a.refcount(b) == 1              # untouched
        a.ref(b)
        a.free([b, b])                         # two refs: now legal
        assert a.free_blocks == 4
        a.assert_invariants()


class TestStateManagerMatching:
    def cfg(self):
        return KVCacheConfig(num_layers=2, num_kv_heads=2, head_dim=16,
                             block_size=4, num_blocks=16)

    def test_release_then_identical_prompt_matches(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 11))           # 10 tokens, 2 full blocks
        sm.build_batch([(0, list(prompt))], token_budget=16)
        first_blocks = list(sm.seqs[0].blocks[:2])
        sm.release(0)
        assert sm.allocator.cached_free_blocks == 2   # full blocks cached
        n = sm.match_prefix(1, list(prompt))
        assert n == 8                          # block-aligned prefix
        assert sm.seqs[1].blocks == first_blocks      # same physical ids
        assert sm.seqs[1].seen_tokens == 8
        assert sm.seqs[1].cached_tokens == 8
        sm.allocator.assert_invariants()

    def test_live_block_sharing_refcounts(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 11))
        sm.build_batch([(0, list(prompt))], token_budget=16)
        n = sm.match_prefix(1, list(prompt))
        assert n == 8
        shared = sm.seqs[1].blocks
        assert shared == sm.seqs[0].blocks[:2]
        assert all(sm.allocator.refcount(b) == 2 for b in shared)
        sm.release(0)
        assert all(sm.allocator.refcount(b) == 1 for b in shared)
        sm.release(1)
        sm.allocator.assert_invariants()
        assert sm.allocator.free_blocks == sm.allocator.total_blocks

    def test_full_cover_match_queues_cow(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 9))            # exactly 2 blocks
        sm.build_batch([(0, list(prompt))], token_budget=16)
        orig = list(sm.seqs[0].blocks)
        sm.release(0)
        n = sm.match_prefix(1, list(prompt))
        assert n == 7                          # one token left to prefill
        seq = sm.seqs[1]
        assert seq.blocks[0] == orig[0]
        assert seq.blocks[1] != orig[1]        # private COW copy
        assert sm.cow_pending == [(1, orig[1], seq.blocks[1])]
        assert sm.take_cow_copies() == [(orig[1], seq.blocks[1])]
        assert sm.cow_pending == []
        sm.allocator.assert_invariants()

    def test_release_drops_pending_cow(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 9))
        sm.build_batch([(0, list(prompt))], token_budget=16)
        sm.release(0)
        sm.match_prefix(1, list(prompt))
        assert sm.cow_pending
        sm.release(1)                          # dst freed with its owner
        assert sm.cow_pending == []
        sm.allocator.assert_invariants()
        assert sm.allocator.free_blocks == sm.allocator.total_blocks

    def test_eviction_drops_index_entries_leaf_first(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 11))
        sm.build_batch([(0, list(prompt))], token_budget=16)
        sm.release(0)
        assert sm.allocator.cached_free_blocks == 2
        # exhaust the plain free list so allocation evicts ONE cached
        # block; release retired the chain LEAF first, so eviction takes
        # the leaf and the surviving root block is still matchable
        sm.build_batch([(1, list(range(60, 119)))], token_budget=64)
        assert sm.allocator.cached_free_blocks == 1
        assert sm.match_prefix(2, list(prompt)) == 4   # root survived
        sm.allocator.assert_invariants()

    def test_evicting_whole_chain_empties_index(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 11))
        sm.build_batch([(0, list(prompt))], token_budget=16)
        sm.release(0)
        # allocate everything: both cached blocks evicted (the index
        # now only holds the NEW sequence's live full blocks)
        sm.build_batch([(1, list(range(60, 123)))], token_budget=64)
        assert sm.allocator.cached_free_blocks == 0
        assert set(sm._hash_index.values()) <= set(sm.seqs[1].blocks)
        assert sm.match_prefix(2, list(prompt)) == 0
        sm.allocator.assert_invariants()

    def test_feedback_token_breaks_chain(self):
        from deepspeed_tpu.inference.ragged.state import FEEDBACK_TOKEN
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        sm.build_batch([(0, [1, 2, 3])], token_budget=16)
        assert not sm.seqs[0].chain_broken
        sm.build_batch([(0, [FEEDBACK_TOKEN])], token_budget=16)
        assert sm.seqs[0].chain_broken
        # deferred token values never enter the hash chain
        assert sm.seqs[0].chain == [1, 2, 3]

    def test_max_pool_take_caps_revivals(self):
        sm = StateManager(self.cfg(), max_seqs=2, prefix_cache=True)
        prompt = list(range(1, 14))           # 3 full blocks
        sm.build_batch([(0, list(prompt))], token_budget=16)
        sm.release(0)
        assert sm.allocator.cached_free_blocks == 3
        n = sm.match_prefix(1, list(prompt), max_pool_take=2)
        assert n == 8                          # capped at 2 revivals
        sm.allocator.assert_invariants()


class TestPrefixCacheParity:
    """Token-for-token parity of prefix_cache on vs off (fp32/greedy is
    exact; fp32/seeded is exact because sampling keys fold
    (uid, position), not step index)."""

    def _shared_traffic(self, seed=0):
        r = np.random.RandomState(seed)
        shared = list(r.randint(1, 128, 24))          # 3 full blocks
        mk_tail = lambda n: list(r.randint(1, 128, n))  # noqa: E731
        return shared, mk_tail

    def _run(self, eng, waves, sp, rng=None):
        out = []
        for wave in waves:
            out.append(eng.generate({u: list(p) for u, p in wave.items()},
                                    sp, rng=rng))
        return out

    def test_greedy_parity_mixed_chunked_traffic(self, model):
        """Sequential waves of prompts sharing a 24-token prefix, budget
        16 so every prompt spans several SplitFuse chunks; the second
        and later waves hit the cache."""
        shared, tail = self._shared_traffic()
        waves = [{0: shared + tail(6)},
                 {1: shared + tail(3), 2: shared + tail(5)},
                 {3: shared + tail(4)}]
        ref = self._run(mk(model, prefix_cache="off", token_budget=16),
                        waves, GREEDY)
        eng = mk(model, token_budget=16)
        got = self._run(eng, waves, GREEDY)
        assert got == ref
        assert eng.timings["cached_tokens"] > 0
        assert eng.timings["prefix_hits"] >= 3
        check_allocator(eng)

    def test_live_sharing_within_one_wave(self, model):
        """Two identical prompts in ONE generate call with a tight
        budget: the later-admitted sequence aliases the earlier one's
        LIVE blocks (registered the step they filled)."""
        shared, tail = self._shared_traffic(1)
        prompt = shared + tail(4)
        waves = [{0: prompt, 1: list(prompt)}]
        ref = self._run(mk(model, prefix_cache="off", token_budget=16),
                        waves, GREEDY)
        eng = mk(model, token_budget=16)
        got = self._run(eng, waves, GREEDY)
        assert got == ref
        assert got[0][0] == got[0][1]          # identical prompts agree
        assert eng.timings["cached_tokens"] > 0
        check_allocator(eng)

    def test_stop_token_parity(self, model):
        shared, tail = self._shared_traffic(2)
        prompt = shared + tail(5)
        base = mk(model, prefix_cache="off").generate(
            {0: list(prompt)}, GREEDY)[0]
        sp = SamplingParams(temperature=0.0, max_new_tokens=50,
                            stop_token=base[2])
        waves = [{0: list(prompt)}, {1: list(prompt)}]
        ref = self._run(mk(model, prefix_cache="off"), waves, sp)
        eng = mk(model)
        got = self._run(eng, waves, sp)
        assert got == ref
        assert got[1][1][-1] == base[2]
        assert eng.timings["cached_tokens"] > 0

    @pytest.mark.parametrize("drive", [
        strict_generate, InferenceEngine.generate], ids=["strict", "ahead"])
    def test_parity_under_either_cadence(self, model, drive):
        """A hit aliases blocks whether the caller feeds each token or
        the engine runs a launch ahead; the cache-less reference is the
        strict loop."""
        shared, tail = self._shared_traffic(3)
        waves = [{0: shared + tail(6)}, {1: shared + tail(2)}]
        off = mk(model, prefix_cache="off", token_budget=16)
        ref = [strict_generate(off, w, GREEDY) for w in waves]
        eng = mk(model, token_budget=16)
        got = [drive(eng, {u: list(p) for u, p in w.items()}, GREEDY)
               for w in waves]
        assert got == ref
        assert eng.timings["cached_tokens"] > 0
        check_allocator(eng)

    def test_eviction_pressure_parity(self, model):
        """Pool of 12 blocks x 8 = 96 tokens with 30-token requests:
        cached blocks MUST be reclaimed mid-run; outputs stay identical
        and accounting stays exact."""
        r = np.random.RandomState(4)
        pA = list(r.randint(1, 128, 24))
        pB = list(r.randint(1, 128, 24))
        waves = [{0: pA + [5, 7]}, {1: pB + [9]}, {2: pA + [3, 1]},
                 {3: pB + [2]}, {4: pA + [8, 8]}]
        kw = dict(num_kv_blocks=12, token_budget=16, max_seqs=2)
        ref = self._run(mk(model, prefix_cache="off", **kw), waves, GREEDY)
        eng = mk(model, **kw)
        got = self._run(eng, waves, GREEDY)
        assert got == ref
        al = eng.state.allocator
        al.assert_invariants()
        assert al.free_blocks == al.total_blocks   # all flushed
        # the tight pool forced evictions, yet some hits still landed
        assert eng.timings["prefix_hits"] > 0

    def test_seeded_sampling_parity(self, model):
        """Seeded sampling on vs off: sampling keys are a pure function
        of (base key, uid, position), so collapsing prefill steps via
        the cache cannot change any sampled token."""
        shared, tail = self._shared_traffic(5)
        waves = [{0: shared + tail(6)}, {1: shared + tail(4)}]
        spr = SamplingParams(temperature=1.0, top_k=8, max_new_tokens=6)
        key = jax.random.PRNGKey(11)
        ref = self._run(mk(model, prefix_cache="off", token_budget=16),
                        waves, spr, rng=key)
        eng = mk(model, token_budget=16)
        got = self._run(eng, waves, spr, rng=key)
        assert got == ref
        assert eng.timings["cached_tokens"] > 0

    def test_full_cover_cow_parity(self, model):
        """Prompt length exactly a block multiple and fully cached: the
        last block is aliased as a copy-on-write private copy, one token
        is re-scheduled, and output parity still holds."""
        shared, _ = self._shared_traffic(6)
        waves = [{0: list(shared)}, {1: list(shared)}, {2: list(shared)}]
        ref = self._run(mk(model, prefix_cache="off"), waves, GREEDY)
        eng = mk(model)
        got = self._run(eng, waves, GREEDY)
        assert got == ref
        # 24-token prompt, full-cover match = 23 tokens served per hit
        assert eng.timings["cached_tokens"] == 2 * (len(shared) - 1)
        check_allocator(eng)

    def test_miss_path_costs_nothing(self, model):
        """Disjoint prompts: hit-rate 0, identical outputs, and the
        engine never dispatches a COW copy (the only device work the
        cache can add)."""
        r = np.random.RandomState(7)
        waves = [{0: list(r.randint(1, 128, 20))},
                 {1: list(r.randint(1, 128, 20))}]
        ref = self._run(mk(model, prefix_cache="off"), waves, GREEDY)
        eng = mk(model)
        got = self._run(eng, waves, GREEDY)
        assert got == ref
        assert eng.timings["cached_tokens"] == 0
        assert eng.timings["prefix_hits"] == 0
        assert eng._cow_fn is None             # COW program never built
        assert eng.timings["prompt_tokens"] == 40

    def test_query_and_counters_during_decode(self, model):
        """query() exposes per-sequence cached_tokens while the request
        is live; engine.timings tracks the cumulative hit counters."""
        shared, tail = self._shared_traffic(8)
        prompt = shared + tail(4)
        eng = mk(model)
        eng.generate({0: list(prompt)}, GREEDY)
        assert eng.query(0)["cached_tokens"] == 0      # flushed
        eng.put(1, list(prompt))
        while not eng.state.seqs.get(1):
            eng.step(sampling=GREEDY)
        q = eng.query(1)
        assert q["cached_tokens"] == 24                # 3 aliased blocks
        assert q["seen_tokens"] >= 24
        tm = eng.timings
        assert tm["cached_tokens"] == 24
        assert tm["prefix_hits"] == 1
        assert tm["prompt_tokens"] == 2 * len(prompt)
        eng.flush(1)
        check_allocator(eng)

    def test_prefix_cache_off_is_inert(self, model):
        eng = mk(model, prefix_cache="off")
        shared, tail = self._shared_traffic(9)
        eng.generate({0: shared + tail(2)}, GREEDY)
        eng.generate({1: shared + tail(2)}, GREEDY)
        assert eng.timings["cached_tokens"] == 0
        assert eng.state._hash_index == {}
        al = eng.state.allocator
        assert al.cached_free_blocks == 0
        assert al.free_blocks == al.total_blocks

    def test_bad_config_value_raises(self, model):
        with pytest.raises(ValueError, match="prefix_cache"):
            mk(model, prefix_cache="maybe")
