"""Flash-attention kernel numerics vs the XLA reference (interpret mode on
CPU; reference analog: tests/unit/ops/transformer — per-kernel numeric
comparison against a python reference, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.layers import causal_attention
from deepspeed_tpu.ops import flash_attention


def qkv(B=2, S=256, H=4, Hkv=4, D=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, S, Hkv, D), dtype))


class TestForward:
    def test_matches_xla(self):
        q, k, v = qkv()
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v)),
            np.asarray(causal_attention(q, k, v)), atol=2e-5)

    def test_gqa(self):
        q, k, v = qkv(Hkv=2)
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v)),
            np.asarray(causal_attention(q, k, v)), atol=2e-5)

    def test_multiple_kv_blocks(self):
        q, k, v = qkv(S=512)
        got = flash_attention(q, k, v, block_q=128, block_k=128)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(causal_attention(q, k, v)),
            atol=2e-5)

    def test_custom_scale(self):
        q, k, v = qkv()
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, scale=0.5)),
            np.asarray(causal_attention(q, k, v, scale=0.5)), atol=2e-5)

    def test_mask_falls_back(self):
        q, k, v = qkv()
        mask = jnp.ones((2, 256))
        out = flash_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(causal_attention(q, k, v)),
            atol=1e-5)

    def test_ragged_seq_falls_back(self):
        q, k, v = qkv(S=100)     # 100 not divisible by any block
        out = flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(causal_attention(q, k, v)),
            atol=1e-5)


class TestBackward:
    @pytest.mark.parametrize("Hkv", [4, 2])
    def test_grads_match(self, Hkv):
        q, k, v = qkv(Hkv=Hkv)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, err_msg=f"d{name}")

    def test_grad_through_jit_and_scan_layers(self):
        """flash inside the transformer stack (remat 'flash' policy)."""
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import build_model

        m = build_model("gpt2", vocab_size=128, num_layers=2, d_model=64,
                        num_heads=4, max_seq_len=128, attention_impl="flash",
                        remat=True, remat_policy="flash")
        eng = ds.initialize(model=m, config={
            "train_micro_batch_size_per_device": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "mesh": {"data": -1}, "steps_per_print": 1000})
        r = np.random.RandomState(0)
        losses = []
        for i in range(5):
            ids = r.randint(0, 128, (eng.train_batch_size, 128))
            losses.append(float(eng.train_batch({"input_ids": ids})["loss"]))
        assert losses[-1] < losses[0]


class TestLongContextStreaming:
    """KV streams through the grid: no VMEM cap, so the kernel must stay
    numerically exact at sequence lengths where the old whole-KV-resident
    variant fell back to XLA."""

    @pytest.mark.nightly
    @pytest.mark.parametrize("S", [4096, 8192])
    def test_long_context_numerics(self, S):
        r = np.random.RandomState(0)
        B, H, Hkv, D = 1, 2, 1, 64
        q = jnp.asarray(r.randn(B, S, H, D), jnp.float32) * 0.3
        k = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32) * 0.3
        v = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32) * 0.3
        o = flash_attention(q, k, v)
        ref = causal_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.nightly
    def test_long_context_grads(self):
        S = 4096
        r = np.random.RandomState(1)
        B, H, Hkv, D = 1, 2, 2, 64
        q = jnp.asarray(r.randn(B, S, H, D), jnp.float32) * 0.3
        k = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32) * 0.3
        v = jnp.asarray(r.randn(B, S, Hkv, D), jnp.float32) * 0.3

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

        g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


def test_cross_length_falls_back():
    """Sk != Sq (diffusers cross-attention) must take the XLA fallback —
    the kernels assume one shared S (caught by round-3 verify)."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 256, 4, 64), jnp.float32) * 0.2
    k = jnp.asarray(r.randn(2, 24, 4, 64), jnp.float32) * 0.2
    v = jnp.asarray(r.randn(2, 24, 4, 64), jnp.float32) * 0.2
    o = flash_attention(q, k, v, causal=False)
    ref = causal_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
