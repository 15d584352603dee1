"""Pallas paged-attention kernel vs the XLA gather formulation
(reference analog: inference/v2/kernels/ragged_ops blocked_flash tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.model import (_paged_attention,
                                           _paged_attention_pallas)
from deepspeed_tpu.inference.ragged.state import RaggedBatch


def _mixed_batch(T=16, max_seqs=4, nblocks=12, bs=8, Hkv=2, D=16, seed=0):
    """Three live sequences at different positions + budget padding."""
    r = np.random.RandomState(seed)
    # seq 0: decode at pos 19 (3 blocks); seq 1: prefill chunk pos 4..11
    # (2 blocks); seq 2: decode at pos 0 (1 block)
    tables = np.full((max_seqs, nblocks), -1, np.int32)
    tables[0, :3] = [5, 2, 9]
    tables[1, :2] = [1, 7]
    tables[2, :1] = [4]
    tok_pos = [(0, 19)] + [(1, p) for p in range(4, 12)] + [(2, 0)]
    T_used = len(tok_pos)
    positions = np.zeros(T, np.int32)
    seq_slot = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    for i, (s, p) in enumerate(tok_pos):
        seq_slot[i], positions[i], valid[i] = s, p, True
    kv = jnp.asarray(r.randn(nblocks + 1, bs, 2, Hkv, D), jnp.float32)
    batch = RaggedBatch(
        token_ids=jnp.zeros(T, jnp.int32),
        positions=jnp.asarray(positions),
        seq_slot=jnp.asarray(seq_slot),
        token_valid=jnp.asarray(valid),
        block_tables=jnp.asarray(tables),
        context_lens=jnp.zeros(max_seqs, jnp.int32),
        logits_idx=jnp.full(max_seqs, -1, jnp.int32),
        n_tokens=T_used, n_seqs=3)
    return kv, batch, bs


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("H", [4, 2])
    def test_matches_xla_gather(self, H):
        kv, batch, bs = _mixed_batch()
        Hkv, D = kv.shape[3], kv.shape[4]
        q = jnp.asarray(np.random.RandomState(1).randn(
            batch.token_ids.shape[0], H, D), jnp.float32)
        scale = 1.0 / np.sqrt(D)
        ref = _paged_attention(kv, q, batch, bs, 4, scale)
        out = _paged_attention_pallas(kv, q, batch, bs, 4, scale)
        valid = np.asarray(batch.token_valid)
        np.testing.assert_allclose(np.asarray(out)[valid],
                                   np.asarray(ref)[valid],
                                   atol=1e-5, rtol=1e-5)

    def test_under_jit_with_bf16(self):
        kv, batch, bs = _mixed_batch()
        kv = kv.astype(jnp.bfloat16)
        D = kv.shape[4]
        q = jnp.asarray(np.random.RandomState(2).randn(
            batch.token_ids.shape[0], 4, D), jnp.bfloat16)
        scale = 1.0 / np.sqrt(D)
        f_ref = jax.jit(lambda kv, q: _paged_attention(kv, q, batch, bs, 4,
                                                       scale))
        f_pal = jax.jit(lambda kv, q: _paged_attention_pallas(
            kv, q, batch, bs, 4, scale))
        valid = np.asarray(batch.token_valid)
        np.testing.assert_allclose(
            np.asarray(f_pal(kv, q)).astype(np.float32)[valid],
            np.asarray(f_ref(kv, q)).astype(np.float32)[valid],
            atol=2e-2, rtol=2e-2)

    def test_engine_forced_pallas_decode_parity(self):
        """Full serving stack with attn_impl=pallas matches the dense
        forward (the greedy-parity bar from test_inference.py)."""
        import deepspeed_tpu  # noqa: F401  (registers presets)
        from tests.test_inference import make_fp32_engine, tiny_model
        from deepspeed_tpu.models import apply

        m = tiny_model()
        eng = make_fp32_engine(m, attn_impl="pallas")
        prompt = list(np.random.RandomState(3).randint(1, 128, 12))
        out = eng.generate({7: prompt}, SamplingParams_greedy())[7]
        # dense reference: greedy continuation with full attention
        ids = list(prompt)
        for _ in range(len(out)):
            logits = apply(m.config, m.params,
                           jnp.asarray([ids], jnp.int32))
            ids.append(int(jnp.argmax(logits[0, -1])))
        assert out == ids[len(prompt):]

    def test_engine_auto_probe_selects_and_serves(self):
        import deepspeed_tpu  # noqa: F401
        from tests.test_inference import make_fp32_engine, tiny_model

        m = tiny_model()
        eng = make_fp32_engine(m, attn_impl="auto")
        prompt = [3, 5, 7, 11]
        out = eng.generate({1: prompt}, SamplingParams_greedy())
        assert len(out[1]) > 0


class TestAliasedBlockTables:
    """Prefix-cache aliasing at the attention level: two sequences'
    block tables referencing the SAME physical block must read identical
    KV from it — attention is a pure gather by block id, so aliasing is
    invisible to the kernel.  Checked against a de-aliased reference
    where the shared content is duplicated into a private block."""

    def _aliased_batch(self, bs=8, Hkv=2, D=16, nblocks=12):
        r = np.random.RandomState(5)
        kv = np.asarray(r.randn(nblocks + 1, bs, 2, Hkv, D), np.float32)
        # both sequences share physical block 4 for positions 0..7, then
        # diverge; the de-aliased reference gives seq 1 a private copy
        # (block 9) with identical content
        kv[9] = kv[4]
        tables = np.full((4, nblocks), -1, np.int32)
        tables[0, :2] = [4, 2]
        tables[1, :2] = [4, 7]
        dealiased = tables.copy()
        dealiased[1, 0] = 9
        # one decode token per sequence, deep enough to read the shared
        # block AND the private tail
        tok_pos = [(0, 12), (1, 14)]
        T = 4
        positions = np.zeros(T, np.int32)
        seq_slot = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        for i, (s, p) in enumerate(tok_pos):
            seq_slot[i], positions[i], valid[i] = s, p, True

        def batch(tab):
            return RaggedBatch(
                token_ids=jnp.zeros(T, jnp.int32),
                positions=jnp.asarray(positions),
                seq_slot=jnp.asarray(seq_slot),
                token_valid=jnp.asarray(valid),
                block_tables=jnp.asarray(tab),
                context_lens=jnp.zeros(4, jnp.int32),
                logits_idx=jnp.full(4, -1, jnp.int32),
                n_tokens=2, n_seqs=2)
        return jnp.asarray(kv), batch(tables), batch(dealiased), bs, valid

    @pytest.mark.parametrize("impl", [_paged_attention,
                                      _paged_attention_pallas])
    def test_shared_block_reads_identical_kv(self, impl):
        kv, aliased, dealiased, bs, valid = self._aliased_batch()
        D = kv.shape[4]
        q = jnp.asarray(np.random.RandomState(6).randn(
            aliased.token_ids.shape[0], 4, D), jnp.float32)
        scale = 1.0 / np.sqrt(D)
        out_alias = impl(kv, q, aliased, bs, 4, scale)
        out_ref = impl(kv, q, dealiased, bs, 4, scale)
        np.testing.assert_allclose(np.asarray(out_alias)[valid],
                                   np.asarray(out_ref)[valid],
                                   atol=1e-6, rtol=1e-6)


class TestCarriedCache:
    """The layer scan carries the stacked cache ``[L, rows, ...]`` in
    place and every layer addresses its own rows in it
    (``model._layer_tables``); a cache in host memory keeps the scanned
    form of the parent commit, one layer sliced out at a time.  The two
    forms are one piece of mathematics: same logits, same cache."""

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
                  jnp.asarray(r.uniform(0.01, 0.03, shape[:-1]),
                              jnp.float32))
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
    def _forward(m, kv, batch, bs, monkeypatch=None, **kw):
        from deepspeed_tpu.inference import model as im
        if kw.get("kv_host"):
            # this backend cannot run in-program host transfers; the
            # move between memory spaces is not what is compared
            monkeypatch.setattr(im.jax, "device_put",
                                lambda x, *a, **k: x)
        def f(params, kv):
            return im.ragged_forward(m.config, params, kv, batch, bs, 4,
                                     **kw)
        return f, jax.jit(f)(m.params, kv)

    @pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
    @pytest.mark.parametrize("kv_quant", [False, True],
                             ids=["fp", "int8kv"])
    def test_carried_matches_streamed(self, kv_quant, attn_impl,
                                      monkeypatch):
        m, kv, batch, written = self._inputs(kv_quant)
        f_c, (logits_c, kv_c) = self._forward(m, kv, batch, self.BS,
                                              attn_impl=attn_impl)
        f_s, (logits_s, kv_s) = self._forward(
            m, kv, batch, self.BS, monkeypatch, attn_impl=attn_impl,
            kv_host=True)
        # which form each took: the pool is in the scan's carry, or
        # among its scanned inputs and outputs
        n_pool = 2 if kv_quant else 1
        for f, carried in ((f_c, True), (f_s, False)):
            scan = [e for e in jax.make_jaxpr(f)(m.params, kv).eqns
                    if e.primitive.name == "scan"][-1]
            assert scan.params["num_carry"] == 1 + n_pool * carried
        rows = np.asarray(logits_idx_rows(batch))
        np.testing.assert_allclose(np.asarray(logits_c)[rows],
                                   np.asarray(logits_s)[rows],
                                   rtol=1e-6, atol=1e-6)
        for new_c, new_s, old in zip(jax.tree.leaves(kv_c),
                                     jax.tree.leaves(kv_s),
                                     jax.tree.leaves(kv)):
            new_c, new_s, old = map(np.asarray, (new_c, new_s, old))
            assert new_c.shape == old.shape
            np.testing.assert_array_equal(new_c, new_s)
            trash = old.shape[1] - 1
            for li in range(self.L):
                changed = {(int(b), int(o)) for b, o in zip(*np.nonzero(
                    (new_c[li] != old[li]).reshape(
                        old.shape[1], old.shape[2], -1).any(-1)))}
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


def logits_idx_rows(batch):
    return np.nonzero(np.asarray(batch.logits_idx) >= 0)[0]


def SamplingParams_greedy():
    from deepspeed_tpu.inference import SamplingParams
    return SamplingParams(temperature=0.0, max_new_tokens=6)
