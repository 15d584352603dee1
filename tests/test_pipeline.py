"""Pipeline-parallel tests (reference analogs: tests/unit/pipe/ —
partition/schedule correctness, PP-vs-DP loss parity)."""

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model
from deepspeed_tpu.runtime import partition_balanced


def base_cfg(**over):
    c = {"train_micro_batch_size_per_device": 4,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "steps_per_print": 1000}
    c.update(over)
    return c


class TestPipelineParity:
    def test_eval_matches_dp(self):
        m = build_model("gpt2", vocab_size=128, num_layers=4, d_model=64,
                        num_heads=4, max_seq_len=32, seed=2)
        eng_pp = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4}))
        eng_dp = ds.initialize(model=m, config=base_cfg(mesh={"data": 8}))
        ids = np.random.RandomState(0).randint(0, 128, (8, 32))
        a = float(eng_pp.eval_batch({"input_ids": ids}))
        b = float(eng_dp.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-3)

    def test_eval_matches_dp_1f1b(self):
        """1F1B engines evaluate through the forward-only (gpipe) path
        (loss_fn.eval_fn); the loss must still match plain DP."""
        m = build_model("gpt2", vocab_size=128, num_layers=4, d_model=64,
                        num_heads=4, max_seq_len=32, seed=2)
        eng_pp = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4,
                      "schedule": "1f1b"}))
        eng_dp = ds.initialize(model=m, config=base_cfg(mesh={"data": 8}))
        ids = np.random.RandomState(0).randint(0, 128, (8, 32))
        a = float(eng_pp.eval_batch({"input_ids": ids}))
        b = float(eng_dp.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-3)

    def test_training_descends(self):
        m = build_model("gpt2", vocab_size=128, num_layers=4, d_model=64,
                        num_heads=4, max_seq_len=32)
        eng = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4}))
        r = np.random.RandomState(1)
        losses = []
        for i in range(8):
            ids = r.randint(0, 128, (eng.train_batch_size, 32))
            losses.append(float(eng.train_batch({"input_ids": ids})["loss"]))
        assert losses[-1] < losses[0]

    def test_microbatch_count_invariance(self):
        """Loss is a per-token average — invariant to M (schedule shape)."""
        m = build_model("gpt2", vocab_size=128, num_layers=2, d_model=32,
                        num_heads=4, max_seq_len=32, seed=7)
        ids = np.random.RandomState(2).randint(0, 128, (32, 32))
        vals = []
        for M in (2, 4):
            eng = ds.initialize(model=m, config=base_cfg(
                mesh={"data": 4, "pipe": 2},
                train_micro_batch_size_per_device=8,
                pipeline={"stages": 2, "num_microbatches": M}))
            vals.append(float(eng.eval_batch({"input_ids": ids})))
        assert vals[0] == pytest.approx(vals[1], rel=1e-4)

    def test_layers_sharded_over_pipe(self):
        m = build_model("gpt2", vocab_size=128, num_layers=4, d_model=64,
                        num_heads=4, max_seq_len=32)
        eng = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4}))
        assert "pipe" in str(eng.param_specs["blocks"]["attn"]["wq"])

    def test_indivisible_layers_raise(self):
        m = build_model("gpt2", vocab_size=128, num_layers=3, d_model=32,
                        num_heads=4, max_seq_len=32)
        with pytest.raises(ValueError, match="divisible"):
            ds.initialize(model=m, config=base_cfg(
                mesh={"data": 4, "pipe": 2},
                pipeline={"stages": 2, "num_microbatches": 2}))


class TestPartitionBalanced:
    """(reference: partition_balanced runtime/utils.py:583, used by
    PipelineModule partition_method='parameters')."""

    def test_uniform(self):
        assert partition_balanced([1, 1, 1, 1], 2) == [0, 2, 4]

    def test_weighted(self):
        bounds = partition_balanced([10, 1, 1, 1, 1, 10], 2)
        # balanced split puts the two heavy ends in different parts
        assert bounds[0] == 0 and bounds[-1] == 6
        w = [10, 1, 1, 1, 1, 10]
        parts = [sum(w[bounds[i]:bounds[i + 1]]) for i in range(2)]
        assert max(parts) <= 14

    def test_more_parts_than_items(self):
        assert partition_balanced([1, 1], 4) == [0, 1, 2, 2, 2]


class Test1F1B:
    """True 1F1B (eager-gradient custom VJP): numerics match gpipe and
    DP, activation memory is bounded by the stage count, not M
    (reference: schedule.py:189 TrainSchedule, num_pipe_buffers :313)."""

    def _model(self, layers=4, seed=2):
        return build_model("gpt2", vocab_size=128, num_layers=layers,
                           d_model=64, num_heads=4, max_seq_len=32,
                           seed=seed)

    def test_grads_match_gpipe(self):
        m = self._model()
        ids = np.random.RandomState(0).randint(0, 128, (16, 32))
        engs = {}
        for sched in ("gpipe", "1f1b"):
            engs[sched] = ds.initialize(model=m, config=base_cfg(
                train_micro_batch_size_per_device=8,
                mesh={"data": 2, "pipe": 4},
                pipeline={"stages": 4, "num_microbatches": 4,
                          "schedule": sched}))
        outs = {}
        for sched, eng in engs.items():
            mtr = eng.train_batch({"input_ids": ids})
            outs[sched] = (float(mtr["loss"]), float(mtr["grad_norm"]))
        assert outs["1f1b"][0] == pytest.approx(outs["gpipe"][0], rel=1e-4)
        assert outs["1f1b"][1] == pytest.approx(outs["gpipe"][1], rel=1e-3)

    def test_training_descends_1f1b(self):
        m = self._model()
        eng = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4,
                      "schedule": "1f1b"}))
        r = np.random.RandomState(1)
        losses = []
        for i in range(8):
            ids = r.randint(0, 128, (eng.train_batch_size, 32))
            losses.append(float(eng.train_batch({"input_ids": ids})["loss"]))
        assert losses[-1] < losses[0]

    def test_1f1b_bounds_activation_memory(self):
        """With M >> S, 1f1b's compiled temp memory stays well below
        gpipe's (ring of min(M, 2S-1) stashes vs M live boundaries)."""
        import jax.numpy as jnp
        from deepspeed_tpu.comm.mesh import MeshTopology
        from deepspeed_tpu.parallel.pipeline import make_pipelined_loss_fn
        from deepspeed_tpu.config.config import MeshConfig

        m = build_model("gpt2", vocab_size=128, num_layers=2, d_model=64,
                        num_heads=4, max_seq_len=32, remat=True)
        topo = MeshTopology.build(MeshConfig(data=4, pipe=2))
        M = 8
        temps = {}
        ids = np.random.RandomState(0).randint(0, 128, (32, 32))
        for sched in ("gpipe", "1f1b"):
            loss_fn = make_pipelined_loss_fn(m.config, topo, M,
                                             schedule=sched)
            # one compile per schedule IS the measurement here
            # (comparing gpipe vs 1f1b compiled temp memory)
            g = jax.jit(jax.grad(lambda p: loss_fn(  # tpulint: disable=retrace-hazard
                p, {"input_ids": jnp.asarray(ids)}, None)))
            mem = g.lower(m.params).compile().memory_analysis()
            temps[sched] = mem.temp_size_in_bytes
        assert temps["1f1b"] < 0.6 * temps["gpipe"], temps

    def test_pipe_with_seq_parallel(self):
        """pipe x seq composes: Ulysses a2a inside the pipeline
        shard_map; eval parity with plain DP."""
        m = self._model(layers=2)
        eng = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=8,
            mesh={"data": 1, "pipe": 2, "seq": 4},
            pipeline={"stages": 2, "num_microbatches": 2}))
        eng_dp = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=2,
            mesh={"data": 8}))
        ids = np.random.RandomState(3).randint(0, 128, (16, 32))
        a = float(eng.eval_batch({"input_ids": ids}))
        b = float(eng_dp.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-3)

    def test_pipe_seq_1f1b_trains(self):
        m = self._model(layers=2)
        eng = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=8,
            mesh={"data": 1, "pipe": 2, "seq": 4},
            pipeline={"stages": 2, "num_microbatches": 2,
                      "schedule": "1f1b"}))
        r = np.random.RandomState(5)
        losses = []
        for i in range(6):
            ids = r.randint(0, 128, (eng.train_batch_size, 32))
            losses.append(float(eng.train_batch({"input_ids": ids})["loss"]))
        assert losses[-1] < losses[0]


class TestPipelineMoE:
    """pipe x expert parallelism (gpipe), including the MoE aux loss
    (reference: l_aux folded into the LM loss, sharded_moe.py)."""

    def _model(self):
        return build_model("mixtral-tiny", vocab_size=256, num_layers=4,
                           d_model=64, num_heads=4, num_kv_heads=2,
                           d_ff=128, num_experts=4, max_seq_len=32,
                           capacity_factor=4.0, seed=2)

    def test_eval_matches_plain_moe(self):
        m = self._model()
        ids = np.random.RandomState(0).randint(0, 256, (8, 32))
        eng_pp = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=8,
            mesh={"data": 1, "pipe": 2, "expert": 4},
            pipeline={"stages": 2, "num_microbatches": 2,
                      "schedule": "gpipe"}))
        eng_ep = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=2,
            mesh={"data": 2, "expert": 4}))
        a = float(eng_pp.eval_batch({"input_ids": ids}))
        b = float(eng_ep.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-3)

    def test_trains(self):
        m = self._model()
        eng = ds.initialize(model=m, config=base_cfg(
            train_micro_batch_size_per_device=8,
            mesh={"data": 1, "pipe": 2, "expert": 4},
            pipeline={"stages": 2, "num_microbatches": 2,
                      "schedule": "gpipe"}))
        ids = np.random.RandomState(1).randint(0, 256,
                                               (eng.train_batch_size, 32))
        losses = [float(eng.train_batch({"input_ids": ids})["loss"])
                  for _ in range(6)]
        assert losses[-1] < losses[0]

    def test_1f1b_moe_matches_gpipe(self):
        """1F1B's eager VJP carries the aux cotangent too: loss and
        grad norm match gpipe+MoE."""
        m = self._model()
        outs = {}
        ids = np.random.RandomState(3).randint(0, 256, (8, 32))
        for sched in ("gpipe", "1f1b"):
            eng = ds.initialize(model=m, config=base_cfg(
                train_micro_batch_size_per_device=8,
                mesh={"data": 1, "pipe": 2, "expert": 4},
                pipeline={"stages": 2, "num_microbatches": 2,
                          "schedule": sched}))
            mtr = eng.train_batch({"input_ids": ids})
            outs[sched] = (float(mtr["loss"]), float(mtr["grad_norm"]))
        assert outs["1f1b"][0] == pytest.approx(outs["gpipe"][0],
                                                rel=1e-4)
        assert outs["1f1b"][1] == pytest.approx(outs["gpipe"][1],
                                                rel=1e-3)


class TestBloomPipeline:
    """ALiBi + word-embedding-layernorm models (BLOOM) under PP — the
    stage-0 embed applies ln_embed and every stage's attention carries
    the ALiBi bias (previously a loud reject)."""

    def _model(self, seed=3):
        return build_model("bloom-tiny", vocab_size=128, num_layers=4,
                           d_model=64, num_heads=4, max_seq_len=32,
                           seed=seed)

    def test_eval_matches_dp(self):
        m = self._model()
        eng_pp = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4}))
        eng_dp = ds.initialize(model=m, config=base_cfg(mesh={"data": 8}))
        ids = np.random.RandomState(0).randint(0, 128, (8, 32))
        a = float(eng_pp.eval_batch({"input_ids": ids}))
        b = float(eng_dp.eval_batch({"input_ids": ids}))
        assert a == pytest.approx(b, rel=1e-3)

    def test_training_descends_1f1b(self):
        m = self._model()
        eng = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 4},
            pipeline={"stages": 4, "num_microbatches": 4,
                      "schedule": "1f1b"}))
        ids = np.random.RandomState(1).randint(0, 128,
                                               (eng.train_batch_size, 32))
        losses = [float(eng.train_batch({"input_ids": ids})["loss"])
                  for _ in range(4)]
        assert losses[-1] < losses[0]

    def test_alibi_pipe_x_seq_composes(self):
        """ALiBi now composes with pipe x seq (head-offset-aware slopes
        inside the per-shard Ulysses a2a); parity covered in
        test_sequence_parallel.TestAlibiSequenceParallel."""
        m = self._model()
        eng = ds.initialize(model=m, config=base_cfg(
            mesh={"data": 2, "pipe": 2, "seq": 2},
            pipeline={"stages": 2, "num_microbatches": 2},
            sequence_parallel={"size": 2}))
        ids = np.random.RandomState(0).randint(0, 128, (8, 32))
        assert np.isfinite(float(eng.eval_batch({"input_ids": ids})))
