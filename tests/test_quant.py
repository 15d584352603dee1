"""Quantization + ZeRO++ tests (reference analogs:
tests/unit/ops/quantizer/, tests/unit/runtime/zero/test_zeropp.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.ops.quant import (QuantizedTensor, dequantize, quantize,
                                     quantized_all_gather,
                                     quantized_psum_scatter,
                                     quantized_reduction)
from tests.simple_model import make_batch, make_mlp


class TestQuantize:
    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("symmetric", [True, False])
    def test_roundtrip_error(self, bits, symmetric):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        qt = quantize(x, bits=bits, num_groups=64, symmetric=symmetric)
        y = dequantize(qt)
        assert y.shape == x.shape and y.dtype == x.dtype
        # quantization noise bound: half an LSB of the per-group range
        qmax = 2 ** (bits - 1) - 1
        scale_bound = np.abs(np.asarray(x)).reshape(64, -1).max(1) / qmax
        err = np.abs(np.asarray(y - x)).reshape(64, -1).max(1)
        assert (err <= scale_bound * (1.01 if symmetric else 2.02)).all()

    def test_int4_packing_halves_bytes(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1024,))
        q8 = quantize(x, bits=8, num_groups=8)
        q4 = quantize(x, bits=4, num_groups=8)
        assert q4.data.size == q8.data.size // 2

    def test_stochastic_rounding_unbiased(self):
        # one max element pins the scale at 0.01/code; the rest sit
        # exactly mid-step (t = 30.5), where the rounding mode is
        # actually observable — a constant 0.3 quantizes to code 127
        # exactly and both modes agree
        x = jnp.full((4096,), 0.305).at[0].set(1.27)
        qt = quantize(x, bits=8, num_groups=1, stochastic=True,
                      rng=jax.random.PRNGKey(2))
        y = dequantize(qt)[1:]
        # deterministic rounding would give a constant (std 0) biased by
        # half a step; stochastic dithers between the two codes and
        # averages out near the true value
        assert float(y.std()) > 0
        assert abs(float(y.mean()) - 0.305) < 0.002

    def test_quantized_reduction(self):
        xs = [jax.random.normal(jax.random.PRNGKey(i), (256,))
              for i in range(4)]
        qts = [quantize(x, bits=8, num_groups=4) for x in xs]
        got = quantized_reduction(qts)
        want = sum(np.asarray(x) for x in xs) / 4
        np.testing.assert_allclose(got, want, atol=0.05)


class TestQuantizedCollectives:
    def test_quantized_all_gather(self, fsdp8):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
        sharded = jax.device_put(x, fsdp8.sharding("fsdp"))

        def local(v):
            return quantized_all_gather(v, "fsdp", bits=8, gather_dim=0)

        out = jax.jit(shard_map(
            local, mesh=fsdp8.mesh, in_specs=P("fsdp"),
            out_specs=P(), check_vma=False))(sharded)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.05)

    def test_quantized_psum_scatter(self, fsdp8):
        # each rank holds a full (unreduced) tensor; result = sharded sum
        xs = np.stack([np.random.RandomState(i).randn(64, 4)
                       for i in range(8)]).astype(np.float32)
        stacked = jax.device_put(
            jnp.asarray(xs), fsdp8.sharding("fsdp"))

        def local(v):
            return quantized_psum_scatter(v[0], "fsdp", bits=8,
                                          num_groups=8)

        out = jax.jit(shard_map(
            local, mesh=fsdp8.mesh, in_specs=P("fsdp"),
            out_specs=P("fsdp"), check_vma=False))(stacked)
        want = xs.sum(0)
        np.testing.assert_allclose(np.asarray(out), want, atol=0.3)


class TestZeroPP:
    def test_qwz_trains_close_to_exact(self):
        """ZeRO-1 + quantized weight gather must track the exact run
        (reference: test_zeropp.py correctness pattern)."""
        p, ax, loss_fn = make_mlp()
        base = {"train_micro_batch_size_per_device": 4,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "mesh": {"fsdp": 8}, "steps_per_print": 1000}
        runs = {}
        for name, z in (("exact", {"stage": 2}),
                        ("qwz", {"stage": 2, "zero_quantized_weights": True})):
            eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax,
                                config={**base, "zero_optimization": z})
            losses = []
            for i in range(5):
                losses.append(float(eng.train_batch(
                    make_batch(eng.train_batch_size, seed=i))["loss"]))
            runs[name] = losses
        np.testing.assert_allclose(runs["qwz"], runs["exact"], rtol=0.05)
        # but not bit-identical (the quantization must actually be in play)
        assert runs["qwz"] != runs["exact"]

    @pytest.mark.parametrize("stage,mesh", [
        (1, {"fsdp": 8}),
        (2, {"data": 2, "fsdp": 4}),
        (3, {"data": 2, "fsdp": 4}),
        (2, {"data": 2, "fsdp": 2, "tensor": 2}),   # TP auto-sharded
    ])
    def test_qgz_trains_close_to_exact(self, stage, mesh):
        """qgZ: the gradient reduction runs through the int8 reduce-scatter
        collectives (reference: all_to_all_quant_reduce,
        coalesced_collectives.py; test_zeropp.py qgZ cases) and training
        tracks the exact run within quantization tolerance."""
        p, ax, loss_fn = make_mlp()
        base = {"train_micro_batch_size_per_device": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "mesh": mesh, "steps_per_print": 1000}
        runs = {}
        for name, z in (("exact", {"stage": stage}),
                        ("qgz", {"stage": stage,
                                 "zero_quantized_gradients": True})):
            eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax,
                                config={**base, "zero_optimization": z})
            if name == "qgz":
                assert eng._qgz_axes, "qgZ did not engage on this mesh"
            losses = []
            for i in range(5):
                losses.append(float(eng.train_batch(
                    make_batch(eng.train_batch_size, seed=i))["loss"]))
            runs[name] = losses
        np.testing.assert_allclose(runs["qgz"], runs["exact"], rtol=0.05)
        # quantization must actually be in play
        assert runs["qgz"] != runs["exact"]

    def test_qgz_with_gas(self):
        """qgZ under gradient accumulation: per-microbatch quantized
        reduction accumulates in the reduced layout."""
        p, ax, loss_fn = make_mlp()
        base = {"train_micro_batch_size_per_device": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "mesh": {"fsdp": 8}, "steps_per_print": 1000}
        runs = {}
        for name, z in (("exact", {"stage": 2}),
                        ("qgz", {"stage": 2,
                                 "zero_quantized_gradients": True})):
            eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax,
                                config={**base, "zero_optimization": z})
            losses = []
            for i in range(4):
                losses.append(float(eng.train_batch(
                    make_batch(eng.train_batch_size, seed=i))["loss"]))
            runs[name] = losses
        np.testing.assert_allclose(runs["qgz"], runs["exact"], rtol=0.05)

    def test_hpz_secondary_partition(self):
        """hpZ: compute params gather over the small fsdp axis only;
        masters shard over the full data x fsdp world; training matches
        plain stage 3 (reference: test_zeropp.py hpZ cases)."""
        p, ax, loss_fn = make_mlp()
        base = {"train_micro_batch_size_per_device": 4,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "mesh": {"fsdp": 8}, "steps_per_print": 1000}
        runs = {}
        for name, z in (("exact", {"stage": 3}),
                        ("hpz", {"stage": 3, "zero_hpz_partition_size": 2})):
            eng = ds.initialize(loss_fn=loss_fn, params=p, param_axes=ax,
                                config={**base, "zero_optimization": z})
            if name == "hpz":
                assert eng.topology.axis_sizes["fsdp"] == 2
                assert eng.topology.axis_sizes["data"] == 4
                # master leaves pick up the data axis; compute specs don't
                mspec = jax.tree.leaves(
                    eng.master_specs, is_leaf=lambda x: isinstance(x, P))
                assert any("data" in str(s) for s in mspec)
                pspec = jax.tree.leaves(
                    eng.param_specs, is_leaf=lambda x: isinstance(x, P))
                assert not any("data" in str(s) for s in pspec)
            losses = []
            for i in range(5):
                losses.append(float(eng.train_batch(
                    make_batch(eng.train_batch_size, seed=i))["loss"]))
            runs[name] = losses
        np.testing.assert_allclose(runs["hpz"], runs["exact"], rtol=1e-4)


class TestOnebitAllReduce:
    """Packed 1-bit collective (reference: nccl.py compressed_allreduce;
    the 5x-comm claim of docs/_tutorials/onebit-adam.md)."""

    def test_pack_roundtrip(self):
        from deepspeed_tpu.ops.quant import pack_signs, unpack_signs
        x = jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)
        p = pack_signs(x)
        assert p.dtype == jnp.uint8 and p.shape == (8,)
        np.testing.assert_array_equal(np.asarray(unpack_signs(p)),
                                      np.where(np.asarray(x) >= 0, 1, -1))

    def test_wire_volume_32x(self):
        from deepspeed_tpu.ops.quant import pack_signs
        x = jnp.ones(1024, jnp.float32)
        assert pack_signs(x).size * 1 == x.size * 4 // 32

    def test_error_feedback_converges_under_shard_map(self):
        """Mean-allreduce of per-shard vectors through the 1-bit wire:
        with error feedback, the time-average converges to the true
        mean (the unbiasedness the EF buffer exists for)."""
        from jax.sharding import PartitionSpec as P
        from deepspeed_tpu.ops.quant import onebit_all_reduce

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("dp",))
        r = np.random.RandomState(0)
        gs = r.randn(8, 40).astype(np.float32)     # per-shard "grads"
        true_mean = gs.mean(axis=0)

        def local(g, err):
            out, new_err = onebit_all_reduce(g[0], "dp", err[0])
            return out[None], new_err[None]

        f = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp")), check_vma=False))
        err = jnp.zeros((8, 40), jnp.float32)
        g = jnp.asarray(gs)
        acc = np.zeros(40)
        steps = 200
        for _ in range(steps):
            out, err = f(g, err)
            acc += np.asarray(out[0])
        # every shard sees the same reduction
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[7]),
                                   atol=1e-6)
        # EF makes the long-run average track the exact mean
        np.testing.assert_allclose(acc / steps, true_mean, atol=0.05)


class TestMinifloatAndSelective:
    """(reference: csrc/fp_quantizer FP6/FP12 + selective_dequantize)."""

    @pytest.mark.parametrize("fmt,tol", [("fp6_e3m2", 0.15),
                                         ("fp12_e4m7", 0.005)])
    def test_roundtrip_error_bounded(self, fmt, tol):
        from deepspeed_tpu.ops.quant import (minifloat_dequantize,
                                             minifloat_quantize)
        x = jnp.asarray(np.random.RandomState(0).randn(64, 64), jnp.float32)
        qt = minifloat_quantize(x, fmt=fmt)
        y = minifloat_dequantize(qt)
        err = np.abs(np.asarray(y) - np.asarray(x)).max()
        assert err < tol * np.abs(np.asarray(x)).max(), err

    def test_fp6_container_byte_sizes(self):
        from deepspeed_tpu.ops.quant import minifloat_quantize
        x = jnp.ones((64, 64))
        q6 = minifloat_quantize(x, fmt="fp6_e3m2")
        q12 = minifloat_quantize(x, fmt="fp12_e4m7")
        assert q6.data.dtype == jnp.int8 and q12.data.dtype == jnp.int16

    def test_selective_matches_full(self):
        from deepspeed_tpu.ops.quant import (dequantize, quantize,
                                             selective_dequantize)
        E, d, f = 8, 32, 64
        w = jnp.asarray(np.random.RandomState(1).randn(E, d, f), jnp.float32)
        qt = quantize(w, bits=8, num_groups=E * 4)
        rows = jnp.asarray([1, 5, 2])
        sel = selective_dequantize(qt, rows)
        full = dequantize(qt)
        np.testing.assert_allclose(np.asarray(sel),
                                   np.asarray(full)[np.asarray(rows)],
                                   atol=1e-6)

    def test_selective_minifloat(self):
        from deepspeed_tpu.ops.quant import (minifloat_dequantize,
                                             minifloat_quantize,
                                             selective_dequantize)
        E, d = 4, 128
        w = jnp.asarray(np.random.RandomState(2).randn(E, d), jnp.float32)
        qt = minifloat_quantize(w, fmt="fp6_e3m2", num_groups=E * 2)
        sel = selective_dequantize(qt, jnp.asarray([3, 0]))
        full = minifloat_dequantize(qt)
        np.testing.assert_allclose(np.asarray(sel),
                                   np.asarray(full)[[3, 0]], atol=1e-6)

    def test_misaligned_groups_raise(self):
        from deepspeed_tpu.ops.quant import quantize, selective_dequantize
        w = jnp.ones((6, 10))
        qt = quantize(w, bits=8, num_groups=4)    # 4 groups, 6 rows
        with pytest.raises(ValueError, match="align"):
            selective_dequantize(qt, jnp.asarray([0]))


class TestRowwiseQuantize:
    def test_roundtrip_weight_shaped(self):
        from deepspeed_tpu.ops.quant import dequantize, quantize_rowwise

        x = jax.random.normal(jax.random.PRNGKey(2), (32, 96))
        qt = quantize_rowwise(x)
        assert qt.data.shape == x.shape          # no grouped relayout
        y = dequantize(qt, jnp.float32)
        bound = np.abs(np.asarray(x)).max(1) / 127.0
        err = np.abs(np.asarray(y - x)).max(1)
        assert (err <= bound * 0.51).all()

    def test_stacked_weights_use_rowwise(self):
        from deepspeed_tpu.inference.quantization import (_quantize_stacked,
                                                          layer_weight)

        w = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 64))
        qt = _quantize_stacked(w, bits=8)
        assert qt.data.shape == w.shape          # weight-shaped payload
        y = layer_weight(qt, 1, jnp.float32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(w[1]),
                                   rtol=0.02, atol=0.02)


class TestPackedFP6:
    """REAL packed fp6 storage — 0.75 byte/element, four codes per three
    bytes (reference: csrc/fp_quantizer/fp_quantize.cu + the cuda_linear
    FP6 GEMM's prepacked weights; previously emulated at int8 width)."""

    def test_pack_unpack_lossless(self):
        from deepspeed_tpu.ops.quant import _pack_codes, _unpack_codes
        u = jnp.arange(64, dtype=jnp.uint32)[None].repeat(3, 0)
        assert bool((_unpack_codes(_pack_codes(u, 4, 6), 4, 6)
                     == u.astype(jnp.int32)).all())

    def test_roundtrip_and_size(self):
        import numpy as np
        from deepspeed_tpu.ops.quant import (dequantize_rowwise6,
                                             quantize_rowwise6)
        w = jnp.asarray(np.random.RandomState(0).randn(3, 40, 64),
                        jnp.float32)
        qt = quantize_rowwise6(w, lead_dims=1)
        assert qt.layout == "rowwise6"
        assert qt.data.shape == (3, 40, 48)     # 0.75x trailing dim
        wd = dequantize_rowwise6(qt, jnp.float32)
        err = float(jnp.abs(wd - w).max() / jnp.abs(w).max())
        assert err < 0.25, err                  # e3m2 per-row-scale error

    def test_serving_uses_packed_layout(self):
        import jax as J
        import numpy as np
        from deepspeed_tpu.inference import (InferenceConfig,
                                             InferenceEngine,
                                             SamplingParams)
        from deepspeed_tpu.models import build_model
        from deepspeed_tpu.ops.quant import QuantizedTensor
        m = build_model("llama-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2,
                        d_ff=128, max_seq_len=128)
        eng = InferenceEngine(m, InferenceConfig(
            token_budget=32, max_seqs=4, kv_block_size=16,
            num_kv_blocks=64, param_dtype=jnp.float32,
            kv_dtype=jnp.float32, weight_quant="fp6"))
        qts = [q for q in J.tree.leaves(
            eng._quant, is_leaf=lambda x: isinstance(x, QuantizedTensor))
            if isinstance(q, QuantizedTensor)]
        assert qts and all(q.layout == "rowwise6" for q in qts)
        q = eng._quant["blocks"]["attn"]["wq"]
        assert abs(q.data.nbytes / np.prod(q.shape) - 0.75) < 0.01
        out = eng.generate({0: [5, 17, 99, 3]},
                           SamplingParams(temperature=0.0,
                                          max_new_tokens=6))
        assert len(out[0]) == 6


class TestPackedFP12:
    def test_pack_unpack_lossless(self):
        from deepspeed_tpu.ops.quant import _pack_codes, _unpack_codes
        u = jnp.arange(4096, dtype=jnp.uint32)[None]
        assert bool((_unpack_codes(_pack_codes(u, 2, 12), 2, 12)
                     == u.astype(jnp.int32)).all())

    def test_roundtrip_size_and_serving(self):
        import numpy as np
        from deepspeed_tpu.inference import (InferenceConfig,
                                             InferenceEngine,
                                             SamplingParams)
        from deepspeed_tpu.models import build_model
        from deepspeed_tpu.ops.quant import (dequantize_rowwise12,
                                             quantize_rowwise12)
        w = jnp.asarray(np.random.RandomState(0).randn(3, 40, 64),
                        jnp.float32)
        qt = quantize_rowwise12(w, lead_dims=1)
        assert qt.layout == "rowwise12"
        assert qt.data.shape == (3, 40, 96)     # 1.5 byte/element
        err = float(jnp.abs(dequantize_rowwise12(qt, jnp.float32)
                            - w).max() / jnp.abs(w).max())
        assert err < 0.01, err                  # e4m7 precision
        m = build_model("llama-tiny", vocab_size=128, num_layers=2,
                        d_model=64, num_heads=4, num_kv_heads=2,
                        d_ff=128, max_seq_len=128)
        base = dict(token_budget=32, max_seqs=4, kv_block_size=16,
                    num_kv_blocks=64, param_dtype=jnp.float32,
                    kv_dtype=jnp.float32)
        gr = SamplingParams(temperature=0.0, max_new_tokens=8)
        ref = InferenceEngine(m, InferenceConfig(**base)).generate(
            {0: [5, 17, 99, 3]}, gr)[0]
        out = InferenceEngine(m, InferenceConfig(**base,
                                                 weight_quant="fp12")
                              ).generate({0: [5, 17, 99, 3]}, gr)[0]
        assert out == ref      # 11-bit sign-mag codes: greedy-exact here
