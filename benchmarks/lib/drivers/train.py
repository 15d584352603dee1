"""Driver of the training jobs (traffic ``kind: train``): the whole
train step through ``ds.initialize`` -> ``engine.train_batch``, fed by a
running host input pipeline, compared with the configuration's plain
reference during set-up."""

import os
import queue
import threading
import time

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import (ROOT, CompileWatch, device_block,
                                   load_module, note, start_trace,
                                   stop_trace)


class InputPipeline:
    """Packed sequences from the seeded token stream, cut on the host by a
    running thread; the stream is cycled."""

    def __init__(self, seed, vocab, batch, seq, batches=32, depth=4):
        self.stream = T.token_stream(seed, vocab, batches * batch * seq)
        self.shape = (batch, seq)
        self.q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="bench-input")
        self._t.start()

    def batch_at(self, i):
        b, s = self.shape
        n = b * s
        k = (i * n) % (len(self.stream) - n + 1)
        return {"input_ids": self.stream[k:k + n].reshape(b, s).copy()}

    def _run(self):
        i = 0
        while not self._stop.is_set():
            item = self.batch_at(i)
            while not self._stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            i += 1

    def stop(self):
        self._stop.set()
        self._t.join()


def run(ctx):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm import MeshTopology
    from deepspeed_tpu.config import MeshConfig
    from benchmarks.lib.weights import (make_model, spread_over,
                                        transformer_config)

    args, config, job, setup = ctx["args"], ctx["config"], ctx["traffic"], \
        ctx["setup"]
    devs = ctx["devices"]
    watch = CompileWatch()
    layout = config["train"]
    extra = {}
    if layout.get("remat"):
        extra = {"remat": True,
                 "remat_policy": layout.get("remat_policy", "nothing")}
    cfg = transformer_config(config, **extra)
    seq = int(job["seq_len"])
    per_chip = int(layout["sequences_per_chip"])
    batch = per_chip * len(devs)
    topo = MeshTopology.build(MeshConfig(**layout["mesh"]), devices=devs)
    shard = None
    if len(devs) > 1:
        axis = max(layout["mesh"], key=layout["mesh"].get)
        shard = spread_over(topo.mesh, axis)
    model = make_model(cfg, args.seed, dtype=None, shardings_for=shard)
    jax.block_until_ready(model.params)
    setup.mark("weights")

    pipe = InputPipeline(args.seed, cfg.vocab_size, batch, seq)
    first = pipe.batch_at(0)
    opt = job["optimizer"]
    lr1 = opt["lr"] * min(1.0, 1.0 / max(1, opt["warmup_steps"]))

    # ---- the plain reference, before the engine takes its memory -------
    refspec = config["reference"]
    ref = load_module(os.path.join(ROOT, refspec["file"]), "bench_reference")
    ids = first["input_ids"]
    ref_loss2 = None
    if "adamw_step" in refspec["compares"]:
        ref_loss1, ref_params = ref.adamw_step(
            model.params, ids, config, lr=lr1, beta1=opt["betas"][0],
            beta2=opt["betas"][1], eps=opt["eps"],
            weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"])
        ref_loss2 = ref.loss(ref_params, ids, config)
        del ref_params
    else:
        ref_loss1 = ref.loss(model.params, ids, config)
    setup.mark("reference")

    engine = ds.initialize(model=model, topology=topo, config={
        "train_micro_batch_size_per_device": per_chip,
        "optimizer": {"type": "adamw", "params": {
            "lr": opt["lr"], "betas": opt["betas"], "eps": opt["eps"],
            "weight_decay": opt["weight_decay"]}},
        "scheduler": {"type": "WarmupLR", "params": {
            "warmup_min_lr": 0.0, "warmup_max_lr": opt["lr"],
            "warmup_num_steps": opt["warmup_steps"],
            "warmup_type": "linear"}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": int(layout["zero_stage"])},
        "gradient_clipping": opt["clip_norm"],
        "steps_per_print": 1 << 30,
        "seed": args.seed % (2 ** 31),
    })
    # the engine keeps the model object; its fp32 init copy (2 GB at
    # pythia-1.4b-d6) is no longer needed once the train state is built
    model.params = None
    del model
    if engine.train_batch_size != batch:
        raise SystemExit(f"global batch {engine.train_batch_size} != {batch}")
    setup.mark("engine")

    # ---- steps 1 and 2 on the SAME batch: compile, warm up, compare ----
    sys_loss1 = float(engine.train_batch(first)["loss"])
    sys_loss2 = float(engine.train_batch(first)["loss"])
    for _ in range(int(job.get("warmup_steps", 2))):
        jax.block_until_ready(engine.train_batch(first)["loss"])
    setup.mark("compile_and_warmup")

    tol = refspec["tolerance"]
    checks = {"loss_step1": (sys_loss1, ref_loss1, tol["loss_rel"])}
    if ref_loss2 is not None:
        checks["loss_step2"] = (sys_loss2, ref_loss2, tol["loss_rel"])
        # the update itself: how far the loss moved, system against reference
        checks["loss_drop"] = (sys_loss1 - sys_loss2, ref_loss1 - ref_loss2,
                               tol["drop_rel"])
    compared = {k: {"system": s, "reference": r,
                    "rel": abs(s - r) / max(abs(r), 1e-12), "tol": t,
                    "ok": bool(abs(s - r) <= t * max(abs(r), 1e-12))}
                for k, (s, r, t) in checks.items()}
    note("reference", file=refspec["file"], compared=compared)

    collectives = None
    if args.trace:
        from benchmarks.lib.trace import hlo_collectives
        step_fn = engine._pick_train_step()
        staged = engine.shard_batch(first)
        text = step_fn.lower(engine.state, staged,
                             jax.random.PRNGKey(0)).compile().as_text()
        collectives = hlo_collectives(text)
        note("collectives_in_hlo", **collectives)
        setup.mark("hlo_text")

    def compiles():
        snap = engine.metrics_snapshot()
        c = snap.get("training_compiles_total", 0)
        c = c.get("value", 0) if isinstance(c, dict) else c
        return float(c), watch.compiles

    # ---- the measured window -------------------------------------------
    trace_dir = ctx["trace_dir"] if args.trace else None
    trace_steps = int(job.get("trace_steps", 3))
    before = compiles()
    setup_s = setup.total()
    note("setup", seconds=setup_s, parts=setup.parts,
         compile=watch.snapshot(), cache=ctx["cache_size"]())
    steps, losses, pending = [], [], None
    span = start_trace(trace_dir) if args.trace else None
    t_open = time.perf_counter()
    t_close = t_open + args.seconds
    i = 0
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        with jax.profiler.TraceAnnotation("bench.input.wait"):
            b = pipe.q.get()
        with jax.profiler.TraceAnnotation("bench.train.dispatch", step=i):
            m = engine.train_batch(b)
        if pending is not None:
            # block on the PREVIOUS step after dispatching this one, as a
            # trainer that logs its loss does: the device never waits
            with jax.profiler.TraceAnnotation("bench.train.wait", step=i - 1):
                losses.append(float(pending[1]["loss"]))
            steps.append((pending[0], time.perf_counter()))
        pending = (now, m)
        i += 1
        if span is not None and len(steps) >= trace_steps:
            jax.block_until_ready(m["loss"])
            steps.append((now, time.perf_counter()))
            losses.append(float(m["loss"]))
            pending = None
            # the device is drained: the span closes on its whole work
            stop_trace(span)
            span = None
    if pending is not None:
        losses.append(float(pending[1]["loss"]))
        steps.append((pending[0], time.perf_counter()))
    if span is not None:
        stop_trace(span)
    pipe.stop()
    after = compiles()

    # a step's time is the gap between consecutive completions (steps are
    # pipelined one deep, so start-to-end would count a step twice)
    ends = [e for _, e in steps]
    done = [(s, e) for s, e in steps if e <= t_close] or steps[:1]
    span = done[-1][1] - done[0][0]
    tokens_per_step = batch * seq
    rec = {
        "kind": "train",
        "setup_s": setup_s,
        "window": {"t_open": t_open, "t_close": t_close,
                   "seconds": args.seconds},
        "steps": steps, "steps_done": done, "losses": losses,
        "step_gaps_s": [b - a for a, b in zip(ends, ends[1:])],
        "tokens_per_step": tokens_per_step, "span_s": span,
        "seq_len": seq, "chips": len(devs),
        "window_compiles": (after[0] - before[0]) + (after[1] - before[1]),
        "hlo_collectives": collectives,
        "trace_dir": trace_dir,
        "compared": compared,
        "attempted": len(steps),
        "failed": sum(1 for x in losses if not np.isfinite(x)),
    }
    rec["correct"] = bool(all(c["ok"] for c in compared.values())
                          and rec["failed"] == 0
                          and rec["window_compiles"] == 0)
    note("window", steps=len(steps), steps_in_window=len(done),
         span_s=span, tokens_per_step=tokens_per_step,
         first_loss=losses[0], last_loss=losses[-1],
         window_compiles=rec["window_compiles"])
    rec["device"] = device_block(devs)
    return rec
