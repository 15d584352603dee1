"""MoE tests (reference analogs: tests/unit/moe/test_moe.py —
gating/capacity/aux-loss correctness, expert-parallel training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model
from deepspeed_tpu.parallel import moe as M


class TestGating:
    def test_top1_routes_to_argmax(self):
        logits = jnp.array([[5.0, 0, 0, 0], [0, 5.0, 0, 0], [0, 0, 5.0, 0]])
        out = M.top_k_gating(logits, top_k=1, capacity=2)
        routed = np.asarray(out.dispatch.sum(axis=2))   # [T, E]
        np.testing.assert_array_equal(
            routed, [[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, 1, 0]])
        assert float(out.dropped) == 0.0

    def test_capacity_drops_overflow(self):
        # all 4 tokens want expert 0, capacity 2 -> 2 dropped
        logits = jnp.tile(jnp.array([[5.0, 0.0]]), (4, 1))
        out = M.top_k_gating(logits, top_k=1, capacity=2)
        assert float(out.dispatch.sum()) == 2.0
        assert float(out.dropped) == pytest.approx(0.5)

    def test_top2_normalized_combine(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
        out = M.top_k_gating(logits, top_k=2, capacity=16)
        sums = np.asarray(out.combine.sum(axis=(1, 2)))
        np.testing.assert_allclose(sums, 1.0, atol=1e-5)

    def test_positions_within_capacity(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
        cap = 8
        out = M.top_k_gating(logits, top_k=2, capacity=cap)
        per_slot = np.asarray(out.dispatch.sum(axis=0))   # [E, C]
        assert per_slot.max() <= 1.0 + 1e-6               # one token per slot
        assert out.dispatch.shape == (64, 4, cap)

    def test_aux_loss_balanced_vs_skewed(self):
        rng = jax.random.PRNGKey(0)
        balanced = jax.random.normal(rng, (256, 4)) * 0.01
        skewed = jnp.concatenate(
            [jnp.full((256, 1), 5.0), jnp.zeros((256, 3))], axis=1)
        a = M.top_k_gating(balanced, 1, 256).aux_loss
        b = M.top_k_gating(skewed, 1, 256).aux_loss
        assert float(a) == pytest.approx(1.0, rel=0.05)   # E * (1/E)^2 * E
        assert float(b) > float(a)

    def test_capacity_formula(self):
        # ceil(64 tokens * k=2 * cf=1.25 / 8 experts) = 20
        assert M.capacity_for(64, 8, 2, 1.25) == 20
        assert M.capacity_for(4, 8, 1, 1.0, min_capacity=4) == 4


class TestExperts:
    def test_moe_ffn_shapes(self):
        kg, ke, kx = jax.random.split(jax.random.PRNGKey(0), 3)
        gp, _ = M.gate_init(kg, 32, 4)
        ep, _ = M.experts_init(ke, 4, 32, 64)
        x = jax.random.normal(kx, (2, 8, 32))
        y, metrics = M.moe_ffn(gp, ep, x, top_k=2, capacity_factor=2.0)
        assert y.shape == x.shape
        assert "moe_aux_loss" in metrics

    def test_single_expert_equals_dense(self):
        """E=1, k=1, ample capacity: MoE == plain FFN with that expert."""
        kg, ke, kx = jax.random.split(jax.random.PRNGKey(0), 3)
        gp, _ = M.gate_init(kg, 16, 1)
        ep, _ = M.experts_init(ke, 1, 16, 32)
        x = jax.random.normal(kx, (1, 4, 16))
        y, _ = M.moe_ffn(gp, ep, x, top_k=1, capacity_factor=8.0,
                         activation=jax.nn.gelu)
        ref = jax.nn.gelu(x[0] @ ep["wi"][0]) @ ep["wo"][0]
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(ref),
                                   atol=1e-5)


class TestEngineIntegration:
    def test_expert_parallel_training(self):
        m = build_model("mixtral-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        num_experts=4, capacity_factor=2.0)
        eng = ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 2, "expert": 4},
            "steps_per_print": 1000})
        # expert weights actually sharded over the expert axis
        spec = eng.param_specs["blocks"]["experts"]["wi"]
        assert "expert" in str(spec)
        r = np.random.RandomState(0)
        losses = []
        for i in range(8):
            ids = r.randint(0, 128, (eng.train_batch_size, 32))
            met = eng.train_batch({"input_ids": ids})
            losses.append(float(met["loss"]))
        assert losses[-1] < losses[0]
        assert "aux/moe_aux_loss" in met

    def test_ep_matches_dense_layout(self):
        """Same MoE model: expert-parallel vs replicated-expert layouts
        produce identical losses (layout invariance)."""
        m = build_model("mixtral-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        num_experts=4, capacity_factor=2.0, seed=11)
        cfg = {"train_micro_batch_size_per_device": 2,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
               "steps_per_print": 1000}
        e1 = ds.initialize(model=m, config={**cfg, "mesh": {"data": 2,
                                                            "expert": 4}})
        e2 = ds.initialize(model=m, config={**cfg, "mesh": {"data": 8}})
        ids = np.random.RandomState(3).randint(0, 128, (8, 32))
        a = float(e1.eval_batch({"input_ids": ids}))
        b = float(e2.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-5)


class TestScatterDispatch:
    """Index-form (megablox-style) dispatch vs the GShard dense-mask
    einsum specification."""

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_einsum_dispatch(self, top_k):
        import jax
        from deepspeed_tpu.parallel.moe import (experts_init, gate_init,
                                                moe_ffn)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        E, dm, dff = 4, 16, 32
        gp, _ = gate_init(k1, dm, E)
        ep, _ = experts_init(k2, E, dm, dff)
        x = jax.random.normal(k3, (2, 24, dm))
        outs = {}
        for mode in ("einsum", "scatter"):
            y, m = moe_ffn(gp, ep, x, top_k=top_k, capacity_factor=0.3,
                           min_capacity=2, dispatch_mode=mode)
            outs[mode] = (np.asarray(y), float(m["moe_aux_loss"]),
                          float(m["moe_dropped"]))
        np.testing.assert_allclose(outs["scatter"][0], outs["einsum"][0],
                                   atol=1e-5, rtol=1e-5)
        assert outs["scatter"][1] == pytest.approx(outs["einsum"][1])
        assert outs["scatter"][2] == pytest.approx(outs["einsum"][2])
        # tight capacity actually dropped something — the parity covers
        # the drop path too
        assert outs["einsum"][2] > 0

    def test_gradients_match(self):
        import jax
        from deepspeed_tpu.parallel.moe import (experts_init, gate_init,
                                                moe_ffn)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        E, dm, dff = 4, 8, 16
        gp, _ = gate_init(k1, dm, E)
        ep, _ = experts_init(k2, E, dm, dff)
        x = jax.random.normal(k3, (1, 16, dm))

        grads = {}
        for mode in ("einsum", "scatter"):
            def loss(gp, ep):
                y, m = moe_ffn(gp, ep, x, top_k=2, capacity_factor=2.0,
                               dispatch_mode=mode)
                return (y ** 2).sum() + m["moe_aux_loss"]
            grads[mode] = jax.grad(loss, argnums=(0, 1))(gp, ep)
        for a, b in zip(jax.tree.leaves(grads["einsum"]),
                        jax.tree.leaves(grads["scatter"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestRaggedDispatch:
    """dispatch_mode='ragged': dropless megablox-style grouped GEMM
    (jax.lax.ragged_dot over expert-sorted tokens — the cutlass
    moe_gemm analog)."""

    def test_matches_einsum_when_nothing_drops(self):
        from deepspeed_tpu.parallel import moe as M

        kg, ke, kx = jax.random.split(jax.random.PRNGKey(0), 3)
        E, dm, dff, B, S = 4, 32, 64, 2, 16
        gp, _ = M.gate_init(kg, dm, E)
        ep, _ = M.experts_init(ke, E, dm, dff)
        x = jax.random.normal(kx, (B, S, dm), jnp.float32)
        kw = dict(top_k=2, min_capacity=4, activation=jax.nn.gelu,
                  gated=False)
        # capacity_factor huge -> the einsum path drops nothing, so the
        # dropless ragged path must agree exactly
        y_ein, m_ein = M.moe_ffn(gp, ep, x, capacity_factor=float(E),
                                 dispatch_mode="einsum", **kw)
        y_rag, m_rag = M.moe_ffn(gp, ep, x, capacity_factor=float(E),
                                 dispatch_mode="ragged", **kw)
        np.testing.assert_allclose(np.asarray(y_ein), np.asarray(y_rag),
                                   rtol=2e-5, atol=2e-5)
        # einsum averages per-sequence aux losses, ragged computes one
        # global statistic — equal in expectation, not bitwise
        np.testing.assert_allclose(float(m_ein["moe_aux_loss"]),
                                   float(m_rag["moe_aux_loss"]),
                                   rtol=2e-2)
        assert float(m_rag["moe_dropped"]) == 0.0

    def test_dropless_under_skewed_routing(self):
        """Every token contributes even when one expert takes nearly all
        traffic (the capacity paths would drop)."""
        from deepspeed_tpu.parallel import moe as M

        kg, ke, kx = jax.random.split(jax.random.PRNGKey(3), 3)
        E, dm, dff = 4, 16, 32
        gp, _ = M.gate_init(kg, dm, E)
        # bias the gate hard toward expert 0
        gp = {"kernel": gp["kernel"].at[:, 0].add(10.0)}
        ep, _ = M.experts_init(ke, E, dm, dff)
        x = jax.random.normal(kx, (1, 32, dm))
        y, m = M.moe_ffn(gp, ep, x, top_k=1, capacity_factor=1.0,
                         min_capacity=2, activation=jax.nn.gelu,
                         gated=False, dispatch_mode="ragged")
        assert float(m["moe_dropped"]) == 0.0
        # no token got zeroed out
        assert np.all(np.abs(np.asarray(y)).sum(axis=-1) > 0)

    def test_model_config_plumbs_ragged(self):
        from deepspeed_tpu.models import build_model
        from deepspeed_tpu.models.transformer import apply

        m = build_model("mixtral-tiny", vocab_size=64, num_layers=2,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=48,
                        max_seq_len=16, moe_dispatch="ragged")
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
        logits = apply(m.config, m.params, ids)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
