"""What the served step and the engine do around the paged-attention
kernel (``tests/test_paged_attention.py`` holds the kernel against the
XLA formulation, shape by shape): the engine's greedy decode on the
kernel, the cache carried in place through the layer scan, a layer of
the stacked pool, the tiles cut once a step for both kinds of layer, and
the counters the tile grid brings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.ragged.state import RaggedBatch
from tests.test_paged_attention import (BS_T, D_T, HKV_T, NBLK_T,
                                        TILE_BATCHES, _built_batch,
                                        _check_tiles, _on_kernel, _on_xla,
                                        _poisoned, _random_pool)


class TestEngineOnTheKernel:
    # (model, engine, the slab of the pool the engine allocates for the
    # kernel): llama-tiny's two heads of 16 lanes; three kv heads under
    # ALiBi (a head and 112 lanes of zeros a key); the same behind an
    # int8 cache, whose codes pack four heads a sublane
    CASES = {
        "lanes": ({}, {}, (2, 128)),
        "heads-and-lanes-alibi": (
            dict(num_heads=6, num_kv_heads=3, d_model=96,
                 position="alibi", attention_impl="xla"), {}, (4, 128)),
        "heads-and-lanes-int8kv": (
            dict(num_heads=6, num_kv_heads=3, d_model=96),
            dict(kv_quant="int8"), (4, 128)),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_engine_forced_pallas_decode_parity(self, case):
        """Full serving stack with attn_impl=pallas matches the dense
        forward (the greedy-parity bar from test_inference.py; behind a
        quantized cache, the engine on the XLA formulation).  The engine
        allocates the kernel's pool with a slab of whole memory tiles
        (``KVCacheConfig.tiled``): the layer's queries, keys and values
        are filled up to it and the output cut back."""
        import deepspeed_tpu  # noqa: F401  (registers presets)
        from tests.test_inference import make_fp32_engine, tiny_model
        from deepspeed_tpu.models import apply

        over, opts, slab = self.CASES[case]
        m = tiny_model(**over)
        eng = make_fp32_engine(m, attn_impl="pallas", **opts)
        data, *scales = jax.tree.leaves(eng.state.kv)
        assert data.shape[-2:] == slab
        assert [a.shape[2:] for a in scales] == [
            (slab[0], 2 * data.shape[2])] * len(scales)
        prompt = list(np.random.RandomState(3).randint(1, 128, 12))
        out = eng.generate({7: prompt}, SamplingParams_greedy())[7]
        if opts:
            plain = make_fp32_engine(m, attn_impl="xla", **opts)
            assert jax.tree.leaves(plain.state.kv)[0].shape[-2:] == (
                m.config.num_kv_heads, m.config.head_dim)
            assert out == plain.generate({7: prompt},
                                         SamplingParams_greedy())[7]
            return
        # dense reference: greedy continuation with full attention
        ids = list(prompt)
        for _ in range(len(out)):
            logits = apply(m.config, m.params,
                           jnp.asarray([ids], jnp.int32))
            ids.append(int(jnp.argmax(logits[0, -1])))
        assert out == ids[len(prompt):]

    @pytest.mark.parametrize("heads,dim,quant,groups,slab", [
        (12, 64, "none", 1, (16, 128)),     # gpt2
        (8, 128, "none", 1, (8, 128)),      # whole tiles as they are
        (4, 128, "none", 1, (4, 128)),
        (1, 128, "none", 1, (2, 128)),      # a bf16 sublane holds two
        (8, 128, "none", 8, (16, 128)),     # one kv head a chip
        (8, 128, "int8", 4, (16, 128)),     # two a chip, four a sublane
        (12, 64, "int8", 1, (16, 128)),
    ])
    def test_a_pool_for_the_kernel_fills_its_slab(self, heads, dim, quant,
                                                  groups, slab):
        """``KVCacheConfig(tiled=True)``: every chip's share of a block's
        ``(Hkv, D)`` slab filled up to Mosaic's memory tiles, the scales
        ``[heads, 2 * bs]`` a block; as the model has them without."""
        from deepspeed_tpu.inference.ragged.state import KVCacheConfig

        kw = dict(num_layers=2, num_kv_heads=heads, head_dim=dim,
                  num_blocks=3, quant=quant, head_groups=groups)
        data, *scales = jax.tree.leaves(jax.eval_shape(
            KVCacheConfig(tiled=True, **kw).kv_zeros))
        assert data.shape == (2, 4, 64, 2) + slab
        assert [a.shape for a in scales] == [(2, 4, slab[0], 128)] * (
            quant != "none")
        plain = jax.tree.leaves(jax.eval_shape(KVCacheConfig(**kw).kv_zeros))
        assert plain[0].shape[-2:] == (heads, dim)


class TestCarriedCache:
    """The layer scan carries the stacked cache ``[L, rows, ...]`` in
    place and every layer addresses its own rows in it
    (``model._layer_tables``)."""

    L, BS, NBLK, T, SEQS = 3, 8, 12, 16, 4

    def _inputs(self, kv_quant, seed=0):
        import deepspeed_tpu  # noqa: F401  (registers presets)
        from tests.test_inference import tiny_model

        m = tiny_model(num_layers=self.L)
        cfg = m.config
        r = np.random.RandomState(seed)
        shape = (self.L, self.NBLK + 1, self.BS, 2, cfg.num_kv_heads,
                 cfg.head_dim)
        # a cache that is nowhere zero and differs from layer to layer:
        # a read of another layer's rows cannot pass for the right one
        if kv_quant:
            kv = (jnp.asarray(r.randint(-127, 128, shape), jnp.int8),
                  jnp.asarray(r.uniform(0.01, 0.03, shape[:2] + (
                      cfg.num_kv_heads, 2 * self.BS)), jnp.float32))
        else:
            kv = jnp.asarray(r.randn(*shape), jnp.float32)
        # seq 0 decodes at 19 (blocks 5, 2, 9); seq 1 prefills the chunk
        # 4..11 of a context whose first 4 tokens are already cached
        # (blocks 1, 7): a chunk that starts at a non-zero offset, in
        # the middle of a block; seq 2 decodes at 0 (block 4); the last
        # six tokens are budget padding.  Every layer shares these ids:
        # block 5 of layer 0 and block 5 of layer 2 are different rows
        tables = np.full((self.SEQS, self.NBLK), -1, np.int32)
        tables[0, :3] = [5, 2, 9]
        tables[1, :2] = [1, 7]
        tables[2, :1] = [4]
        tok_pos = [(0, 19)] + [(1, p) for p in range(4, 12)] + [(2, 0)]
        n = len(tok_pos)
        positions = np.zeros(self.T, np.int32)
        seq_slot = np.zeros(self.T, np.int32)
        valid = np.zeros(self.T, bool)
        for i, (s_, p_) in enumerate(tok_pos):
            seq_slot[i], positions[i], valid[i] = s_, p_, True
        logits_idx = np.full(self.SEQS, -1, np.int32)
        logits_idx[:3] = [0, 8, 9]
        batch = RaggedBatch(
            token_ids=jnp.asarray(r.randint(1, 128, self.T), jnp.int32),
            positions=jnp.asarray(positions),
            seq_slot=jnp.asarray(seq_slot),
            token_valid=jnp.asarray(valid),
            block_tables=jnp.asarray(tables),
            context_lens=jnp.asarray([20, 12, 1, 0], jnp.int32),
            logits_idx=jnp.asarray(logits_idx), n_tokens=n, n_seqs=3)
        # (block, offset) that each live token writes
        written = {(tables[s_, p_ // self.BS], p_ % self.BS)
                   for s_, p_ in tok_pos}
        return m, kv, batch, written

    @staticmethod
    def _forward(m, kv, batch, bs, **kw):
        from deepspeed_tpu.inference import model as im

        def f(params, kv):
            # the model's tree as the engine holds it
            return im.ragged_forward(m.config, im.fold_projections(params),
                                     kv, batch, bs, 4, **kw)
        return f, jax.jit(f)(m.params, kv)

    @pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
    @pytest.mark.parametrize("kv_quant", [False, True],
                             ids=["fp", "int8kv"])
    def test_cache_is_carried_in_place(self, kv_quant, attn_impl):
        m, kv, batch, written = self._inputs(kv_quant)
        f, (logits, new_kv) = self._forward(m, kv, batch, self.BS,
                                            attn_impl=attn_impl)
        # the pool is in the layer scan's carry, beside the activations:
        # not among its scanned inputs and outputs, where each layer
        # would be sliced out of the stack and written back
        n_pool = 2 if kv_quant else 1
        scan = [e for e in jax.make_jaxpr(f)(m.params, kv).eqns
                if e.primitive.name == "scan"][-1]
        assert scan.params["num_carry"] == 1 + n_pool
        rows = np.asarray(logits_idx_rows(batch))
        assert np.isfinite(np.asarray(logits)[rows]).all()
        for new, old in zip(jax.tree.leaves(new_kv), jax.tree.leaves(kv)):
            new, old = np.asarray(new), np.asarray(old)
            assert new.shape == old.shape
            trash = old.shape[1] - 1
            for li in range(self.L):
                diff = new[li] != old[li]
                if diff.ndim == 3:
                    # the scales [rows, Hkv, 2 * bs]: an offset is a lane
                    # of a head's keys' half and of its values'
                    diff = diff.reshape(len(diff), -1, self.BS).swapaxes(
                        1, 2)
                changed = {(int(b), int(o)) for b, o in zip(*np.nonzero(
                    diff.reshape(len(diff), self.BS, -1).any(-1)))}
                # layer li took its tokens in its own rows, its padding
                # in its own trash row, and nothing anywhere else
                assert changed - {(trash, 0)} == written, li
                assert (trash, 0) in changed, li

    def test_layers_do_not_share_rows(self):
        """Two layers, one block id: a step with layer 1's rows of the
        cache scrambled beforehand gives the same layer-0 cache and
        different logits, and a scrambled layer 0 trash row changes
        nothing (padding reads and writes only trash)."""
        m, kv, batch, _ = self._inputs(False)
        f, (logits, new) = self._forward(m, kv, batch, self.BS)
        other = kv.at[1].set(kv[1][::-1])
        logits_o, new_o = jax.jit(f)(m.params, other)
        np.testing.assert_array_equal(np.asarray(new_o[0]),
                                      np.asarray(new[0]))
        rows = np.asarray(logits_idx_rows(batch))
        assert np.abs(np.asarray(logits_o)[rows]
                      - np.asarray(logits)[rows]).max() > 1e-3
        trashed = kv.at[0, -1].set(7.0)
        logits_t, _ = jax.jit(f)(m.params, trashed)
        np.testing.assert_array_equal(np.asarray(logits_t)[rows],
                                      np.asarray(logits)[rows])


class TestTilesInAStep:
    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8kv"])
    @pytest.mark.parametrize("li", [0, 1, 2])
    def test_layer_of_a_stacked_pool(self, li, quant):
        """``layer=(base, rows)`` on a three-layer pool viewed
        ``[L * rows, ...]`` equals the kernel on that layer's own
        slice, and the XLA formulation there."""
        runs, T = TILE_BATCHES["two-chunks"]
        batch, _ = _built_batch(runs, T)
        kv = _random_pool(6, layers=3, quant=quant)
        rows = NBLK_T + 1
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), kv)
        own = jax.tree.map(lambda a: a[li], kv)
        q = jnp.asarray(np.random.RandomState(12).randn(T, 8, D_T),
                        jnp.float32)
        scale = float(1.0 / np.sqrt(D_T))
        stacked = _on_kernel(flat, q, batch, 32, scale,
                             layer=(li * rows, rows))
        alone = _on_kernel(own, q, batch, 32, scale)
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(alone))
        _check_tiles(flat, batch, 8, nb=32, layer=(li * rows, rows),
                     tol=1e-4 if quant else 1e-5)

    @pytest.mark.parametrize("window", [None, 20])
    @pytest.mark.parametrize("name", ["group-edges", "verify-across-groups",
                                      "two-chunks", "decode-only"])
    def test_one_cut_serves_both_kinds_of_layer(self, name, window):
        """The step cuts its tiles once (``ragged_forward`` does, outside
        the layers) and a layer of either kind walks them as they are:
        the kernel finds a window tile's first block itself, and fetches
        each block from the stack's row where it lies.  Every other
        layer's rows, and every row of this layer's that no query needs,
        hold NaN."""
        from deepspeed_tpu.inference.model import _query_tiles
        from deepspeed_tpu.ops.paged_attention import paged_attention

        runs, T = TILE_BATCHES[name]
        batch, _ = _built_batch(runs, T)
        kv = _random_pool(12, layers=3)
        rows = NBLK_T + 1
        flat = kv.reshape((-1,) + kv.shape[2:])
        q = jnp.asarray(np.random.RandomState(5).randn(T, HKV_T * 4, D_T),
                        jnp.float32)
        scale = float(1.0 / np.sqrt(D_T))
        tiles = _query_tiles(flat, batch, BS_T, 32)
        layer = (2 * rows, rows)
        got = jax.jit(paged_attention, static_argnums=(3,),
                      static_argnames=("layer", "window"))(
            _poisoned(flat, batch, window, layer), q, tiles, scale,
            layer=layer, window=window)
        want = _on_xla(kv[2], q, batch, 32, scale, window=window)
        valid = np.asarray(batch.token_valid)
        np.testing.assert_allclose(np.asarray(got)[valid],
                                   np.asarray(want)[valid], atol=1e-5,
                                   rtol=1e-5)


class TestTileCounter:
    """The counter the tile grid brings: ``n_tiles_short``,
    ``n_tiles_long`` and ``tile_fill`` on the ``ds.serve.stage`` span
    and in ``metrics_snapshot()``, counted on the host from the
    schedule (``ops/paged_attention.tile_counts``)."""

    @staticmethod
    def _stage_spans(eng):
        return [e for e in eng.tracer.events()
                if e["name"] == "ds.serve.stage"]

    def test_decode_tokens_and_one_chunk(self):
        import deepspeed_tpu  # noqa: F401  (registers presets)
        from tests.test_inference import make_fp32_engine, tiny_model
        from deepspeed_tpu.inference import SamplingParams

        eng = make_fp32_engine(tiny_model(max_seq_len=256),
                               attn_impl="pallas", token_budget=192,
                               max_seqs=72, num_kv_blocks=160, trace=True)
        sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
        for uid in range(64):
            eng.put(uid, [3 + uid % 50, 7])
        first = eng.step(sampling=sp)
        assert len(first) == 64
        (span,) = self._stage_spans(eng)
        # 64 two-token prompts: 64 short tiles, no long one
        assert (span["args"]["n_tiles_short"], span["args"]["n_tiles_long"],
                span["args"]["tile_fill"]) == (64, 0, 0.0)
        assert "serving_attn_tile_fill" not in eng.metrics_snapshot()
        for uid, tok in first.items():
            eng.put(uid, [tok])
        eng.put(100, list(range(1, 105)))
        assert len(eng.step(sampling=sp)) == 65
        span = self._stage_spans(eng)[-1]
        assert span["args"]["n_tokens"] == 64 + 104
        assert (span["args"]["n_tiles_short"],
                span["args"]["n_tiles_long"]) == (64, 1)
        assert span["args"]["tile_fill"] == pytest.approx(104 / 128)
        snap = eng.metrics_snapshot()
        tiles = snap["serving_attn_tiles_total"]
        assert tiles == {'{height="short"}': 128, '{height="long"}': 1}
        assert snap["serving_attn_tile_fill"] == pytest.approx(104 / 128)
        # a round with nothing to schedule stages nothing: no span, no
        # tile
        assert eng.step(sampling=sp) == {}
        assert len(self._stage_spans(eng)) == 2
        assert eng.metrics_snapshot()["serving_attn_tiles_total"] == tiles

    def test_group_steps_of_the_short_call(self):
        """``kv_steps_full`` on the stage span, the counter
        ``serving_attn_kv_group_steps_total`` and the gauge
        ``serving_attn_kv_group_fill``: the groups the decode
        tokens' call walks in a layer, with
        the group the kernel's own rule gives the engine's shapes."""
        import deepspeed_tpu  # noqa: F401
        from tests.test_inference import make_fp32_engine, tiny_model
        from deepspeed_tpu.inference import SamplingParams
        from deepspeed_tpu.ops.paged_attention import SHORT, kv_group

        eng = make_fp32_engine(tiny_model(max_seq_len=256),
                               attn_impl="pallas", token_budget=192,
                               max_seqs=4, kv_block_size=8,
                               num_kv_blocks=96, trace=True)
        k = kv_group(SHORT, 2, 2, 16, 8, jnp.float32,
                     eng.max_blocks_per_seq)
        assert k == 16
        sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
        # a chunk: no short tile
        eng.put(1, [i % 100 + 1 for i in range(180)])
        eng.put(2, [5, 6, 7])               # a run of three: one block
        first = eng.step(sampling=sp)
        span = self._stage_spans(eng)[-1]["args"]
        assert span["kv_steps_full"] == 1 and "kv_steps_window" not in span
        assert eng.metrics_snapshot()[
            "serving_attn_kv_group_fill"] == pytest.approx(1 / k)
        for uid, tok in first.items():
            eng.put(uid, [tok])
        eng.step(sampling=sp)
        # 181 tokens are 23 blocks, two groups of sixteen; 4 are one
        span = self._stage_spans(eng)[-1]["args"]
        assert span["kv_steps_full"] == 2 + 1
        snap = eng.metrics_snapshot()
        assert snap["serving_attn_kv_group_steps_total"] == {
            '{kind="full"}': 4}
        assert snap["serving_attn_kv_group_fill"] == pytest.approx(
            (1 + 23 + 1) / (4 * k))

    def test_xla_formulation_counts_no_tiles(self):
        import deepspeed_tpu  # noqa: F401
        from tests.test_inference import make_fp32_engine, tiny_model
        from deepspeed_tpu.inference import SamplingParams

        eng = make_fp32_engine(tiny_model(), attn_impl="xla", trace=True)
        eng.put(0, [5, 6, 7])
        eng.step(sampling=SamplingParams(temperature=0.0,
                                         max_new_tokens=4))
        (span,) = self._stage_spans(eng)
        assert "n_tiles_short" not in span["args"]
        assert "kv_steps_full" not in span["args"]
        snap = eng.metrics_snapshot()
        assert snap["serving_attn_tiles_total"] == 0
        assert snap["serving_attn_kv_group_steps_total"] == 0
        assert "serving_attn_kv_group_fill" not in snap


def logits_idx_rows(batch):
    return np.nonzero(np.asarray(batch.logits_idx) >= 0)[0]


def SamplingParams_greedy():
    from deepspeed_tpu.inference import SamplingParams
    return SamplingParams(temperature=0.0, max_new_tokens=6)
