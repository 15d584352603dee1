"""Sparse gradient reduction (reference analogs: runtime/sparse_tensor.py
+ engine.py sparse_allreduce_bucket; tests/unit sparse grad tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.runtime.sparse_grads import (default_capacity,
                                                is_sparse_leaf, sparse_psum)


class TestSparsePsum:
    def _run(self, per_shard, capacity):
        """8 shards, each with a row-sparse [V, d] grad."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("dp",))
        V, d = 32, 4
        g = jnp.stack(per_shard)                          # [8, V, d]

        def local(g):
            return sparse_psum(g[0], "dp", capacity)[None]

        out = jax.jit(shard_map(
            local, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False))(g)
        return np.asarray(out[0])

    def test_matches_dense_psum_when_capacity_suffices(self):
        r = np.random.RandomState(0)
        V, d = 32, 4
        shards = []
        for s in range(8):
            g = np.zeros((V, d), np.float32)
            rows = r.choice(V, 5, replace=False)
            g[rows] = r.randn(5, d)
            shards.append(jnp.asarray(g))
        got = self._run(shards, capacity=5)
        want = np.sum([np.asarray(s) for s in shards], axis=0)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_overfull_drops_lowest_mass_rows(self):
        V, d = 32, 4
        g = np.zeros((V, d), np.float32)
        g[0] = 100.0                 # heavy row survives
        g[1] = 0.001                 # light row dropped at capacity 1
        shards = [jnp.asarray(g)] * 8
        got = self._run(shards, capacity=1)
        np.testing.assert_allclose(got[0], np.full(d, 800.0), atol=1e-4)
        np.testing.assert_allclose(got[1], np.zeros(d), atol=1e-6)

    def test_leaf_predicate_and_capacity(self):
        assert is_sparse_leaf(("vocab", "embed"))
        assert not is_sparse_leaf(("embed", "vocab"))
        assert not is_sparse_leaf(None)
        assert default_capacity(batch_tokens=4096, vocab=50257) == 4096
        assert default_capacity(batch_tokens=10 ** 9, vocab=50257) == 50257


class TestEngineSparseGradients:
    def test_training_matches_dense(self):
        """sparse_gradients=True reproduces dense training numerics on an
        UNTIED-embedding LM (the lookup grad touches <= tokens-per-shard
        rows, so the capacity is lossless; tied heads would be dense)."""
        from deepspeed_tpu.models import build_model

        m = build_model("llama-tiny", vocab_size=512, num_layers=2,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                        max_seq_len=16, seed=0)
        ids = np.random.RandomState(0).randint(0, 512, (16, 16))
        losses = {}
        for sparse in (False, True):
            eng = ds.initialize(model=m, config={
                "train_micro_batch_size_per_device": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "sparse_gradients": sparse,
                "mesh": {"data": 8}, "steps_per_print": 1000})
            ls = [float(eng.train_batch({"input_ids": ids})["loss"])
                  for _ in range(4)]
            losses[sparse] = ls
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=2e-4, atol=2e-4)
        assert losses[True][-1] < losses[True][0]

    def test_tied_embeddings_rejected_unless_opted_out(self):
        """Tied models get dense vocab grads: sparse_gradients is a hard
        ConfigError by default, and degrades loudly only under
        allow_feature_degradation."""
        from deepspeed_tpu.config.config import ConfigError
        from deepspeed_tpu.models import build_model

        m = build_model("gpt2", vocab_size=256, num_layers=2, d_model=32,
                        num_heads=4, max_seq_len=16)
        base = {
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "sparse_gradients": True,
            "mesh": {"data": 8}, "steps_per_print": 1000}
        with pytest.raises(ConfigError, match="ties embeddings"):
            ds.initialize(model=m, config=dict(base))
        eng = ds.initialize(model=m, config=dict(
            base, allow_feature_degradation=True))
        assert eng._sparse_axes == ()

    def test_head_bias_leaf_not_sparse(self):
        # a 1-D vocab leaf (lm_head bias) receives DENSE gradients
        assert not is_sparse_leaf(("vocab",))

    @pytest.mark.nightly
    def test_matches_dense_under_stage2_fsdp(self):
        """Stage-2 + fsdp reduce-scatters the table grad first; the
        capacity must cover rows merged from every scattered peer."""
        from deepspeed_tpu.models import build_model

        m = build_model("llama-tiny", vocab_size=512, num_layers=2,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                        max_seq_len=16, seed=0)
        ids = np.random.RandomState(0).randint(0, 512, (16, 16))
        losses = {}
        for sparse in (False, True):
            eng = ds.initialize(model=m, config={
                "train_micro_batch_size_per_device": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "sparse_gradients": sparse,
                "zero_optimization": {"stage": 2},
                "mesh": {"data": 2, "fsdp": 4}, "steps_per_print": 1000})
            losses[sparse] = [
                float(eng.train_batch({"input_ids": ids})["loss"])
                for _ in range(4)]
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=2e-4, atol=2e-4)
