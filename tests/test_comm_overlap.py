"""Overlapped (T3, arxiv 2401.16677) + quantized (EQuARX, arxiv
2506.17615) collectives — docs/SERVING.md "Overlapped & quantized
collectives".

The contract under test, rung by rung of the exactness ladder:

* exact tiles are BITWISE-identical to the serial collective (matmul+
  allreduce, matmul+allgather, reduce-scatter, allreduce — any tile
  count), and the serving/training integrations inherit that: greedy
  and seeded TP serving tokens match `comm_overlap="off"` exactly, and
  the training loss under the comm grad path is bitwise-invariant
  across tile counts;
* the ppermute ring rung is exact arithmetic in a rotated order (close,
  not bitwise);
* the quantized rung stays inside its documented error bound across
  axis sizes {2,4,8} x bits {4,8} x bf16/f32, including the
  non-divisible-shape padding path;
* the wire telemetry reconciles: a quantized op's modeled bytes are
  exactly bits/8 of the exact op's;
* a merged tracemerge timeline of a capture window shows the named
  tile-comm scopes on device activity (validate_merged_trace).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm import overlap as ov
from deepspeed_tpu.ops.quant import (quantized_all_reduce,
                                     quantized_psum_scatter)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def _smap(fn, mesh, in_specs, out_specs=P()):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

class TestPrimitives:
    @pytest.mark.parametrize("tiles", [1, 2, 4, 6])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matmul_allreduce_bitwise(self, devices, tiles, dtype):
        mesh = _mesh(8)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(48, 64), dtype)
        w = jnp.asarray(rng.randn(64, 32), dtype)
        specs = (P(None, "x"), P("x", None))
        serial = _smap(lambda a, b: jax.lax.psum(
            (a @ b).astype(dtype), "x"), mesh, specs)
        tiled = _smap(lambda a, b: ov.overlapped_matmul_allreduce(
            a, b, "x", tiles=tiles), mesh, specs)
        ref, got = np.asarray(serial(x, w)), np.asarray(tiled(x, w))
        np.testing.assert_array_equal(got, ref)

    def test_matmul_allreduce_ring_exact_not_bitwise(self, devices):
        mesh = _mesh(8)
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(32, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64, 16), jnp.float32)
        specs = (P(None, "x"), P("x", None))
        serial = _smap(lambda a, b: jax.lax.psum(a @ b, "x"), mesh, specs)
        ring = _smap(lambda a, b: ov.overlapped_matmul_allreduce(
            a, b, "x", tiles=4, strategy="ring"), mesh, specs)
        ref, got = np.asarray(serial(x, w)), np.asarray(ring(x, w))
        # same summands, rotated order: tight but not necessarily exact
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    def test_matmul_allgather_bitwise(self, devices):
        mesh = _mesh(8)
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(10, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64, 48), jnp.float32)
        specs = (P(), P(None, "x"))
        serial = _smap(lambda a, b: jax.lax.all_gather(
            a @ b, "x", axis=1, tiled=True), mesh, specs)
        tiled = _smap(lambda a, b: ov.overlapped_matmul_allgather(
            a, b, "x", tiles=5), mesh, specs)
        np.testing.assert_array_equal(np.asarray(tiled(x, w)),
                                      np.asarray(serial(x, w)))
        # and both equal the unsharded product (gather moves, never rounds)
        np.testing.assert_allclose(np.asarray(tiled(x, w)),
                                   np.asarray(x) @ np.asarray(w),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("scatter_dim", [0, 1])
    def test_reduce_scatter_bitwise(self, devices, scatter_dim):
        mesh = _mesh(8)
        rng = np.random.RandomState(3)
        g = jnp.asarray(rng.randn(16, 24), jnp.float32)
        out_spec = P("x") if scatter_dim == 0 else P(None, "x")
        serial = _smap(lambda a: jax.lax.psum_scatter(
            a, "x", scatter_dimension=scatter_dim, tiled=True),
            _mesh(8), P(), out_spec)
        tiled = _smap(lambda a: ov.overlapped_reduce_scatter(
            a, "x", scatter_dim=scatter_dim, tiles=4), mesh, P(), out_spec)
        np.testing.assert_array_equal(np.asarray(tiled(g)),
                                      np.asarray(serial(g)))

    def test_all_reduce_bitwise_and_ring(self, devices):
        mesh = _mesh(8)
        rng = np.random.RandomState(4)
        h = jnp.asarray(rng.randn(13, 7), jnp.float32)   # 13 % 8 != 0
        serial = _smap(lambda a: jax.lax.psum(a, "x"), mesh, P())
        tiled = _smap(lambda a: ov.overlapped_all_reduce(
            a, "x", tiles=4), mesh, P())
        ref = np.asarray(serial(h))
        np.testing.assert_array_equal(np.asarray(tiled(h)), ref)
        ring = _smap(lambda a: ov.ring_all_reduce(a, "x"), mesh, P())
        np.testing.assert_allclose(np.asarray(ring(h)), ref,
                                   rtol=1e-5, atol=1e-5)

    def test_ring_all_gather_bitwise(self, devices):
        mesh = _mesh(8)
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
        serial = _smap(lambda a: jax.lax.all_gather(
            a, "x", axis=0, tiled=True), mesh, P("x"), P())
        ring = _smap(lambda a: ov.ring_all_gather(a, "x", axis=0),
                     mesh, P("x"), P())
        np.testing.assert_array_equal(np.asarray(ring(x)),
                                      np.asarray(serial(x)))

    def test_rs_tile_dim_never_scattered(self):
        # tiling the scattered dim would permute the output layout
        assert ov._rs_tile_dim((16, 24), 0, 4) == 1
        assert ov._rs_tile_dim((16, 24), 1, 4) == 0
        assert ov._rs_tile_dim((16,), 0, 4) is None
        assert ov._resolve_tiles(48, 5) == 4


# --------------------------------------------------------------------------
# quantized-collective error bounds (satellite): axis {2,4,8} x bits
# {4,8} x bf16/f32, divisible and padded shapes
# --------------------------------------------------------------------------

class TestQuantizedBounds:
    @pytest.mark.parametrize("n", [2, 4, 8])
    @pytest.mark.parametrize("bits", [4, 8])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("rows", [16, 13])   # 13: padding path
    def test_quantized_all_reduce_bound(self, devices, n, bits, dtype,
                                        rows):
        mesh = _mesh(n)
        rng = np.random.RandomState(n * bits + rows)
        x = jnp.asarray(rng.randn(rows, 24), dtype)
        exact = _smap(lambda a: jax.lax.psum(a, "x"), mesh, P())
        quant = _smap(lambda a: quantized_all_reduce(
            a, "x", bits=bits, pad=True), mesh, P())
        ref = np.asarray(exact(x), np.float32)
        got = np.asarray(quant(x), np.float32)
        qmax = 2.0 ** (bits - 1) - 1
        # one worst-case half-step per rank on the scatter leg + one on
        # the re-gather, plus the output dtype's own resolution
        bound = (n + 1) * float(np.abs(np.asarray(x, np.float32)).max()) \
            / qmax + np.abs(ref).max() * (2.0 ** -8 if dtype
                                          == jnp.bfloat16 else 2.0 ** -20)
        err = np.abs(got - ref).max()
        assert err <= bound, (err, bound, n, bits, dtype, rows)
        # the padded path must not leak padding into the payload shape
        assert got.shape == ref.shape

    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_quantized_psum_scatter_padding_path(self, devices, n):
        mesh = _mesh(n)
        rng = np.random.RandomState(n)
        rows = n + 1                                  # never divisible
        x = jnp.asarray(rng.randn(rows, 8), jnp.float32)
        pad_rows = (-rows) % n
        xp = np.concatenate([np.asarray(x),
                             np.zeros((pad_rows, 8), np.float32)])
        exact = _smap(lambda a: jax.lax.psum_scatter(
            jnp.asarray(xp), "x", scatter_dimension=0, tiled=True),
            mesh, P(), P("x"))
        quant = _smap(lambda a: quantized_psum_scatter(
            a, "x", pad=True), mesh, P(), P("x"))
        ref = np.asarray(exact(x))
        got = np.asarray(quant(x))
        assert got.shape == ref.shape                 # the PADDED shard
        bound = n * float(np.abs(np.asarray(x)).max()) / 127.0 + 1e-6
        assert np.abs(got - ref).max() <= bound

    def test_quantized_psum_scatter_still_asserts_without_pad(self,
                                                              devices):
        mesh = _mesh(4)
        x = jnp.ones((5, 4), jnp.float32)
        with pytest.raises(Exception):
            _smap(lambda a: quantized_psum_scatter(a, "x"),
                  mesh, P(), P("x"))(x)

    def test_wire_bytes_quant_is_bits_over_8(self):
        for op in ("all_reduce", "reduce_scatter", "all_gather"):
            exact = ov.wire_bytes(op, 4096, 4, 8)
            for bits in (4, 8):
                q = ov.wire_bytes(op, 4096, 4, 8, quant_bits=bits)
                assert q == pytest.approx(exact * bits / (8 * 4))
        assert ov.wire_bytes("all_reduce", 100, 4, 1) == 0.0


# --------------------------------------------------------------------------
# serving integration: parity + counters
# --------------------------------------------------------------------------

def _serve_model():
    from deepspeed_tpu.models import build_model

    return build_model("llama-tiny", vocab_size=128, num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=88,
                       max_seq_len=64)


def _serve_engine(comm_overlap="auto", comm_quant=None, topo=True,
                  **kw):
    from deepspeed_tpu.comm.mesh import MeshTopology
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.engine import (InferenceConfig,
                                                InferenceEngine)

    t = MeshTopology.build(MeshConfig(tensor=2, fsdp=4)) if topo else None
    cfg = InferenceConfig(token_budget=16, max_seqs=2, kv_block_size=8,
                          num_kv_blocks=16, attn_impl="xla",
                          param_dtype=jnp.float32, kv_dtype=jnp.float32,
                          comm_overlap=comm_overlap, comm_quant=comm_quant,
                          **kw)
    return InferenceEngine(_serve_model(), cfg, topology=t)


PROMPTS = {0: list(range(1, 9)), 1: [5, 6, 7]}


class TestServingParity:
    @pytest.fixture(scope="class")
    def baseline(self, devices):
        from deepspeed_tpu.inference.sampler import SamplingParams

        eng = _serve_engine("off")
        greedy = eng.generate(
            dict(PROMPTS), SamplingParams(temperature=0.0,
                                          max_new_tokens=6))
        seeded = eng.generate(
            dict(PROMPTS), SamplingParams(temperature=0.8,
                                          max_new_tokens=5),
            rng=jax.random.PRNGKey(7))
        return greedy, seeded

    def test_on_matches_off_greedy_and_seeded(self, baseline):
        from deepspeed_tpu.inference.sampler import SamplingParams

        eng = _serve_engine("on")
        plan = eng._serving_comm
        assert plan is not None and plan.downproj and plan.unembed
        greedy = eng.generate(
            dict(PROMPTS), SamplingParams(temperature=0.0,
                                          max_new_tokens=6))
        assert greedy == baseline[0]
        seeded = eng.generate(
            dict(PROMPTS), SamplingParams(temperature=0.8,
                                          max_new_tokens=5),
            rng=jax.random.PRNGKey(7))
        assert seeded == baseline[1]
        # counters: per step, num_layers down-proj all-reduces + 1
        # unembed gather, all exact; tile accounting mirrors the
        # compiled _resolve_tiles clamp (down-proj rows=16 -> 4 tiles,
        # unembed rows=max_seqs=2 -> clamped to 2)
        snap = eng.metrics.snapshot()
        steps = snap["serving_steps_total"]
        ops = snap["serving_comm_ops_total"]['{kind="exact"}']
        assert ops == steps * (2 + 1)
        assert snap["serving_comm_tiles_total"] == steps * (2 * 4 + 2)
        assert snap["serving_comm_bytes_total"]['{kind="exact"}'] > 0

    def test_auto_resolves_on_under_tp_and_matches(self, baseline):
        from deepspeed_tpu.inference.sampler import SamplingParams

        eng = _serve_engine("auto")
        assert eng._serving_comm is not None
        out = eng.generate(dict(PROMPTS),
                           SamplingParams(temperature=0.0,
                                          max_new_tokens=6))
        assert out == baseline[0]

    def test_on_single_chip_is_loud_noop(self, baseline):
        from deepspeed_tpu.inference.sampler import SamplingParams

        eng = _serve_engine("on", topo=False)
        assert eng._serving_comm is None
        out = eng.generate(dict(PROMPTS),
                           SamplingParams(temperature=0.0,
                                          max_new_tokens=6))
        assert out == baseline[0]
        snap = eng.metrics.snapshot()
        assert snap["serving_comm_ops_total"] == 0
        assert snap["serving_comm_tiles_total"] == 0

    def test_quantized_allreduce_serving(self, baseline):
        from deepspeed_tpu.inference.sampler import SamplingParams

        eng = _serve_engine("on", comm_quant="int8")
        plan = eng._serving_comm
        assert plan.quant_bits == 8
        out = eng.generate(dict(PROMPTS),
                          SamplingParams(temperature=0.0,
                                         max_new_tokens=6))
        # greedy argmax over well-separated toy logits survives the
        # bounded quantization error; the logits-level bound is the
        # quantized-collective test above
        assert out == baseline[0]

    def test_comm_bytes_quant_is_bits_over_8_of_exact(self, devices):
        from deepspeed_tpu.inference.sampler import SamplingParams

        sp = SamplingParams(temperature=0.0, max_new_tokens=4)
        exact = _serve_engine("on")
        exact.generate(dict(PROMPTS), sp)
        quant = _serve_engine("on", comm_quant="int8")
        quant.generate(dict(PROMPTS), sp)
        se, sq = exact.metrics.snapshot(), quant.metrics.snapshot()
        assert se["serving_steps_total"] == sq["serving_steps_total"]
        # the down-projection all-reduce: f32 exact vs int8 wire = 1/4
        e_dp = se["serving_comm_bytes_total"]['{kind="exact"}'] \
            - sq["serving_comm_bytes_total"]['{kind="exact"}']
        q_dp = sq["serving_comm_bytes_total"]['{kind="quant"}']
        assert q_dp == pytest.approx(e_dp * 8 / (8 * 4))
        # the unembed gather never quantizes: identical exact bytes
        assert sq["serving_comm_bytes_total"]['{kind="exact"}'] > 0

    def test_quant_alone_leaves_unembed_with_gspmd(self, devices):
        # comm_overlap="off" + comm_quant: ONE serial quantized
        # all-reduce on the down-projection and nothing else — "off"
        # must not substitute a ppermute ring for the fused gather
        eng = _serve_engine("off", comm_quant="int8")
        plan = eng._serving_comm
        assert plan is not None
        assert plan.quant_bits == 8 and plan.tiles == 1
        assert plan.downproj and not plan.unembed

    def test_config_validation(self):
        from deepspeed_tpu.inference.engine import InferenceConfig, \
            InferenceEngine

        with pytest.raises(ValueError, match="comm_overlap"):
            InferenceEngine(_serve_model(),
                            InferenceConfig(comm_overlap="maybe"))
        with pytest.raises(ValueError, match="comm_quant"):
            InferenceEngine(_serve_model(),
                            InferenceConfig(comm_quant="int2"))


# --------------------------------------------------------------------------
# training integration: comm grad path
# --------------------------------------------------------------------------

def _train_losses(comm_cfg=None, steps=2):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    model = build_model("gpt2", vocab_size=256, num_layers=2, d_model=64,
                        num_heads=4, max_seq_len=64)
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": 2, "fsdp": 4},
        "steps_per_print": 1000,
    }
    if comm_cfg:
        cfg["comm"] = comm_cfg
    eng = ds.initialize(model=model, config=cfg)
    ids = np.random.RandomState(0).randint(
        0, 256, (eng.train_batch_size, 32))
    losses = [float(np.asarray(jax.device_get(
        eng.train_batch({"input_ids": ids})["loss"])))
        for _ in range(steps)]
    return losses, eng


class TestTrainingCommGrads:
    def test_tiled_bitwise_vs_serial_manual_and_close_to_gspmd(
            self, devices):
        base, _ = _train_losses(None)
        t1, e1 = _train_losses({"overlap": True, "tiles": 1})
        t4, e4 = _train_losses({"overlap": True, "tiles": 4})
        # the tentpole's change — tile decomposition — is bitwise
        assert t4 == t1
        # entering the manual region at all reports loss as a mean of
        # shard means (the pre-existing qgZ/1-bit property); the values
        # stay tightly close to the GSPMD scalar
        np.testing.assert_allclose(t4, base, rtol=1e-5)
        assert e4._comm_axes == ("data", "fsdp")
        snap = e4.metrics.snapshot()
        assert snap["training_comm_ops_total"]['{kind="exact"}'] > 0
        assert snap["training_comm_tiles_total"] > \
            snap["training_comm_ops_total"]['{kind="exact"}']

    def test_quantized_allreduce_close_and_quarter_bytes(self, devices):
        t4, e4 = _train_losses({"overlap": True, "tiles": 4})
        q, eq = _train_losses({"overlap": True, "tiles": 4,
                               "quantized_allreduce": "int8"})
        np.testing.assert_allclose(q, t4, rtol=0.05)
        s4, sq = e4.metrics.snapshot(), eq.metrics.snapshot()
        be = s4["training_comm_bytes_total"]['{kind="exact"}']
        bq = sq["training_comm_bytes_total"]['{kind="quant"}']
        # f32 grads on an int8 wire: exactly 1/4 of the exact bytes
        assert bq == pytest.approx(be / 4)

    def test_onebit_optimizer_takes_precedence(self, devices):
        # the documented precedence: a 1-bit optimizer owns the wire;
        # comm settings must not silently disable its compressed
        # reduction
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import build_model

        model = build_model("gpt2", vocab_size=256, num_layers=2,
                            d_model=64, num_heads=4, max_seq_len=64)
        eng = ds.initialize(model=model, config={
            "train_micro_batch_size_per_device": 2,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 4}},
            "comm": {"overlap": True, "tiles": 4},
            "mesh": {"data": 8},
            "steps_per_print": 1000})
        assert eng._onebit_axes == ("data",)
        assert eng._comm_axes == ()

    def test_comm_config_validation(self):
        from deepspeed_tpu.config import Config
        from deepspeed_tpu.config.config import ConfigError

        with pytest.raises(ConfigError):
            Config.from_dict({"comm": {"quantized_allreduce": "int2"}})
        with pytest.raises(ConfigError):
            Config.from_dict({"comm": {"tiles": 0}})


# --------------------------------------------------------------------------
# satellites: Collectives LRU + comms_logger registry mirror
# --------------------------------------------------------------------------

class TestEagerCollectives:
    def test_jit_cache_lru_bounded_and_retrace_counted(self, devices):
        from deepspeed_tpu.comm import Collectives, MeshTopology
        from deepspeed_tpu.config import MeshConfig
        from deepspeed_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        coll = Collectives(MeshTopology.build(MeshConfig(data=8)),
                           metrics=reg)
        for i in range(Collectives._CACHE_CAP + 4):
            coll.all_reduce(jnp.ones((8 + i,), jnp.float32),
                            axis_name="data")
        assert len(coll._cache) == Collectives._CACHE_CAP
        compiles = reg.get(
            "training_comm_collective_compiles_total").value()
        assert compiles == Collectives._CACHE_CAP + 4
        assert reg.get(
            "training_comm_collective_retraces_total").value() == 0
        # the first shape was evicted: re-running it is a retrace
        coll.all_reduce(jnp.ones((8,), jnp.float32), axis_name="data")
        assert reg.get(
            "training_comm_collective_retraces_total").value() == 1
        # LRU, not FIFO: touching an entry protects it from eviction
        survivor_shape = 8 + Collectives._CACHE_CAP + 3
        coll.all_reduce(jnp.ones((survivor_shape,), jnp.float32),
                        axis_name="data")            # touch most-recent
        key = next(k for k in coll._cache if (survivor_shape,) in k)
        assert key in coll._cache

    def test_comms_logger_registry_mirror(self, devices):
        from deepspeed_tpu.comm import Collectives, MeshTopology
        from deepspeed_tpu.comm.comms_logging import comms_logger
        from deepspeed_tpu.config import MeshConfig
        from deepspeed_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        comms_logger.attach_registry(reg)
        comms_logger.configure(enabled=True, prof_all=True)
        try:
            coll = Collectives(MeshTopology.build(MeshConfig(data=8)),
                               metrics=reg)
            coll.all_reduce(jnp.ones((64,), jnp.float32),
                            axis_name="data")
        finally:
            comms_logger.configure(enabled=False)
        snap = reg.snapshot()
        assert snap["training_comm_ops_profiled_total"][
            '{op="all_reduce"}'] == 1
        assert snap["training_comm_time_ms_total"][
            '{op="all_reduce"}'] > 0
        assert snap["training_comm_msg_bytes_total"][
            '{op="all_reduce"}'] == 64 * 4
        # exposition carries the series (flight/Prometheus visibility)
        assert "training_comm_time_ms_total" in reg.prometheus_text()


# --------------------------------------------------------------------------
# bench + merged timeline
# --------------------------------------------------------------------------

class TestBenchAndTimeline:
    def test_overlap_bench_leg_records_gateable_metrics(self, devices):
        from deepspeed_tpu.comm.bench import overlap_bench

        rec = overlap_bench(rows=32, k=128, nmodel=64, tiles=4,
                            trials=2, warmups=1)
        for k in ("comm_serial_ms", "comm_overlapped_ms", "comm_ring_ms",
                  "comm_quant_ms", "comm_overlap_speedup",
                  "comm_ring_speedup", "comm_quant_speedup"):
            assert rec[k] > 0
        assert rec["wire_bytes_quant"] == pytest.approx(
            rec["wire_bytes_exact"] / 4)

    def test_capture_window_merged_timeline_shows_tile_scopes(
            self, devices, tmp_path):
        from deepspeed_tpu.inference.sampler import SamplingParams
        from tools.tracemerge import merge_capture, validate_merged_trace

        eng = _serve_engine("on", profile=str(tmp_path),
                            profile_steps=6)
        eng.generate(dict(PROMPTS),
                     SamplingParams(temperature=0.0, max_new_tokens=8))
        eng.finish_capture()
        assert eng.capture_dirs, "capture window did not complete"
        merged = merge_capture(eng.capture_dirs[0])
        with open(merged) as f:
            obj = json.load(f)
        meta = obj["otherData"]["capture"]
        if not meta.get("profiler", True):
            pytest.skip("jax.profiler unavailable in this build — "
                        "host-only capture (loud by contract)")
        # the overlap measurement bar: schema-valid merged timeline
        # whose DEVICE activity carries the named tile scopes — comm
        # tiles AND the GEMM tiles they interleave with
        problems = validate_merged_trace(
            obj, require_device=True,
            require_scopes=["t3_mm_ar_comm_t0", "t3_mm_ar_gemm_t",
                            "t3_mm_ag_comm_t0"])
        assert problems == [], problems
