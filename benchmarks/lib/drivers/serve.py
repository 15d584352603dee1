"""Driver of the serving mixes (traffic ``kind: closed`` / ``open``):
``InferenceEngine`` behind ``gateway.Gateway`` on loopback in this
process, which holds the chip; the load generator is a child process
that never imports JAX (benchmarks/lib/loadgen.py)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import (ROOT, CompileWatch, device_block,
                                   load_module, note, start_trace,
                                   stop_trace)

TIMING_KEYS = ("schedule_ms", "stage_ms", "device_ms", "wait_ms",
               "readback_ms", "steps", "prompt_tokens", "cached_tokens",
               "generated_tokens", "compiles", "step_retries")


def pow2_buckets(max_context: int, block: int, cap: int):
    """The context buckets (in blocks, powers of two) the engine compiles
    a step for, up to the one that holds ``max_context``."""
    need = -(-max_context // block)
    out, b = [], 1
    while True:
        out.append(min(b, cap))
        if b >= need or b >= cap:
            return out
        b *= 2


def engine_logits(eng, seqs: dict, n_prompt: dict, mbs: int):
    """Logits of the engine's paged path on ``seqs`` ({uid: token list}):
    the first ``n_prompt[uid]`` tokens prefill in one step, the rest are
    fed one at a time through the paged cache (teacher-forced decoding).
    Returns {uid: [rows]}, row i being the logits after token
    ``n_prompt - 1 + i``.  Uses the logits-returning sibling of the
    serving step (``_build_step``), the idiom of chip_smoke.prefill_logits
    and tests/test_inference_tp.py."""
    step = eng._build_step(mbs)
    eng.state.reset_prefix_cache()
    rows = {u: [] for u in seqs}

    def one_step(feed):
        for uid, toks in feed.items():
            eng.put(uid, toks)
        sched = eng._schedule()
        if sorted(u for u, _ in sched) != sorted(feed):
            raise SystemExit("reference sample did not fit one engine step")
        batch = eng._stage(eng.state.build_batch(sched, eng.icfg.token_budget))
        logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv, batch)
        for uid in feed:
            rows[uid].append(np.asarray(logits[eng.state.slot(uid)],
                                        np.float32))

    one_step({u: list(s[:n_prompt[u]]) for u, s in seqs.items()})
    extra = max(len(s) - n_prompt[u] for u, s in seqs.items())
    for i in range(extra):
        one_step({u: [s[n_prompt[u] + i]] for u, s in seqs.items()
                  if n_prompt[u] + i < len(s)})
    for uid in seqs:
        eng.flush(uid)
    eng.state.reset_prefix_cache()
    return rows


def instrument(eng, gw, steps: list, annotate: bool):
    """Per-step records from the benchmark's own files, around the calls
    into the engine: a wrapper of ``step`` and one of ``_schedule``.
    With ``annotate`` also host spans (``bench.*``) for the profiler."""
    import contextlib
    import jax
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if annotate \
        else (lambda name: contextlib.nullcontext())
    tm = eng.timings
    last = {}
    orig_schedule, orig_step = eng._schedule, eng.step

    def schedule(*a, **k):
        with span("bench.engine.schedule"):
            sched = orig_schedule(*a, **k)
        n_tok = ctx = pairs = 0
        for uid, toks in sched:
            seq = eng.state.seqs.get(uid)
            seen = seq.seen_tokens if seq else 0
            n = len(toks)
            n_tok += n
            ctx += seen + n
            pairs += n * seen + n * (n + 1) // 2
        last.update(n_tokens=n_tok, n_seqs=len(sched), ctx_tokens=ctx,
                    qk_pairs=pairs)
        return sched

    between = []      # the open "between steps" span, if any

    def step(*a, **k):
        if between:
            between.pop().__exit__(None, None, None)
        last.clear()
        dev0 = tm["device_ms"] + tm["wait_ms"]
        host0 = tm["schedule_ms"] + tm["stage_ms"] + tm["readback_ms"]
        t0 = time.monotonic()
        with span("bench.engine.step"):
            out = orig_step(*a, **k)
        t1 = time.monotonic()
        if last.get("n_tokens"):
            steps.append({"t0": t0, "t1": t1,
                          "device_ms": tm["device_ms"] + tm["wait_ms"] - dev0,
                          "host_ms": tm["schedule_ms"] + tm["stage_ms"]
                          + tm["readback_ms"] - host0,
                          "emitted": len(out), **last})
        if annotate:
            # from a step's end to the next step's start the engine's
            # thread is idle: the gateway's event loop routes tokens,
            # writes SSE frames and hands the next call over
            between.append(jax.profiler.TraceAnnotation(
                "bench.gateway.between_steps"))
            between[-1].__enter__()
        return out

    eng._schedule, eng.step = schedule, step
    if annotate:
        for obj, name, label in ((eng, "_stage", "bench.engine.stage"),
                                 (eng, "_collect", "bench.engine.collect"),
                                 (eng, "_fetch_tokens", "bench.engine.readback"),
                                 (gw, "_apply", "bench.gateway.apply")):
            if obj is None:
                continue
            orig = getattr(obj, name)

            def wrapped(*a, _o=orig, _l=label, **k):
                with span(_l):
                    return _o(*a, **k)
            setattr(obj, name, wrapped)


def run(ctx):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.gateway import GatewayConfig, spawn_gateway
    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from benchmarks.lib.weights import make_model, transformer_config

    args, config, mix, setup = ctx["args"], ctx["config"], ctx["traffic"], \
        ctx["setup"]
    devs = ctx["devices"]
    watch = CompileWatch()
    sizes = mix["engine"]
    cfg = transformer_config(config)
    block = int(sizes["kv_block_size"])
    model = make_model(cfg, args.seed, dtype=jnp.bfloat16)
    jax.block_until_ready(model.params)
    setup.mark("weights")

    # ---- the plain reference, before the engine takes the KV pool ------
    refspec = config["reference"]
    ref = load_module(os.path.join(ROOT, refspec["file"]), "bench_reference")
    sample = refspec["sample"]
    rng = T.rng_for(args.seed, 9)
    k_dec = int(sample["decode_tokens"])
    seqs = {900000 + i: rng.integers(0, cfg.vocab_size, n + k_dec).tolist()
            for i, n in enumerate(sample["prompt_lens"])}
    n_prompt = {u: len(s) - k_dec for u, s in seqs.items()}
    ref_rows = {u: np.asarray(ref.logits(model.params, np.asarray(s), config)
                              [n_prompt[u] - 1:], np.float32)
                for u, s in seqs.items()}
    setup.mark("reference")

    eng = InferenceEngine(model, InferenceConfig(
        token_budget=int(sizes["token_budget"]),
        max_seqs=int(sizes["max_seqs"]), kv_block_size=block,
        num_kv_blocks=int(sizes["num_kv_blocks"]),
        max_seq_len=int(sizes["max_seq_len"]),
        **config.get("engine_options", {})))
    setup.mark("engine")

    # ---- engine against reference: prefill, then decode through the cache
    max_ctx = max(len(s) for s in seqs.values())
    mbs_ref = pow2_buckets(max_ctx, block, eng.max_blocks_per_seq)[-1]
    got = engine_logits(eng, seqs, n_prompt, mbs_ref)
    tol = refspec["tolerance"]["logits_rel"]
    compared = {}
    for phase, sl in (("prefill", slice(0, 1)), ("decode", slice(1, None))):
        worst = 0.0
        for u in seqs:
            r = ref_rows[u][sl]
            g = np.stack(got[u][sl.start:sl.stop])
            worst = max(worst, float(np.abs(g - r).max() / np.abs(r).max()))
        compared[f"logits_{phase}"] = {
            "system": worst, "reference": 0.0, "rel": worst, "tol": tol,
            "ok": bool(worst <= tol)}
    note("reference", file=refspec["file"], compared=compared,
         sample=sample, races={k: {n: round(1e3 * v, 2) for n, v in r.items()}
                               for k, r in eng.probe_times.items()})
    setup.mark("reference_compare_and_race")

    # ---- warm every context bucket the mix can reach --------------------
    sampling = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    max_context = int(mix["prompt_tokens"]["hi"]) + int(mix["answer_tokens"]["hi"])
    buckets = pow2_buckets(max_context, block, eng.max_blocks_per_seq)
    for b in buckets:
        uid = 800000 + b
        n = min(b * block - 1, int(sizes["max_seq_len"]) - 2)
        eng.put(uid, rng.integers(0, cfg.vocab_size, n).tolist())
        produced = 0
        while produced < 2:
            out = eng.step(sampling=sampling)
            if uid in out:
                produced += 1
                if produced < 2:
                    eng.put(uid, [int(out[uid])])
        eng.flush(uid)
    eng.state.reset_prefix_cache()
    note("warmup", buckets=buckets, compiles=float(eng.timings["compiles"]),
         races={k: min(r, key=r.get) for k, r in eng.probe_times.items()})
    setup.mark("warmup_buckets")

    steps = []
    h = spawn_gateway(eng, GatewayConfig(
        sampling=sampling, max_tokens_cap=1 << 16, install_signals=False))
    instrument(eng, h.gateway, steps, annotate=bool(args.trace))
    eng.reset_metrics()
    setup.mark("gateway")

    # ---- the load generator: a child that never imports JAX -------------
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    warmup_s = float(mix["warmup_s"])
    # the child reads the mix as this run uses it (rehearsal sizes, or the
    # one-off rate of the sweep that defines an open-loop cell)
    if ctx.get("sweep_rate"):
        mix = {**mix, "rate_per_s": float(ctx["sweep_rate"])}
    mix_path = ctx["records_path"] + ".traffic.json"
    with open(mix_path, "w") as f:
        json.dump({k: v for k, v in mix.items() if k != "_file"}, f)
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "lib", "loadgen.py"),
           "--host", h.host, "--port", str(h.port), "--traffic", mix_path,
           "--seed", str(args.seed), "--vocab", str(cfg.vocab_size),
           "--seconds", str(args.seconds), "--warmup", str(warmup_s),
           "--drain", str(mix.get("drain_s", 30)),
           "--out", ctx["records_path"]]
    snaps, events = {}, {}
    opened, closed = threading.Event(), threading.Event()

    def snapshot():
        s = {k: float(eng.timings[k]) for k in TIMING_KEYS}
        s["xla_compiles"] = watch.compiles
        s["t"] = time.monotonic()
        return s

    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env, text=True)

    def reader():
        for line in child.stdout:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            events[ev.get("event")] = ev
            if ev.get("event") == "open":
                snaps["open"] = snapshot()
                opened.set()
            elif ev.get("event") == "close":
                snaps["close"] = snapshot()
                closed.set()
        opened.set()
        closed.set()

    rt = threading.Thread(target=reader, daemon=True, name="bench-loadgen-out")
    rt.start()
    opened.wait()
    setup_s = setup.total()
    setup.mark("loop_warmup")
    note("setup", seconds=setup_s, parts=setup.parts,
         compile=watch.snapshot(), cache=ctx["cache_size"]())
    trace_window = (None, None)
    if args.trace:
        # the window's span lies on the trace's clock and cuts busy_s and
        # window_s; the host's two stamps, inside it, only choose the whole
        # steps of serve_step_roofline
        span = start_trace(ctx["trace_dir"])
        t_tr0 = time.monotonic()
        closed.wait(timeout=float(mix.get("trace_s", 5.0)))
        t_tr1 = time.monotonic()
        stop_trace(span)
        trace_window = (t_tr0, t_tr1)
    closed.wait()
    child.wait()
    rt.join()
    t_end = time.monotonic()
    engine_requests = eng.request_metrics()["requests"]
    device = device_block(devs)
    h.stop()
    if child.returncode != 0 or "done" not in events:
        raise SystemExit(f"load generator failed (exit {child.returncode})")

    done = events["done"]
    w = {"t_open": done["t_open"], "t_close": done["t_close"],
         "seconds": done["t_close"] - done["t_open"]}
    with open(ctx["records_path"]) as f:
        requests = [json.loads(line) for line in f]
    closed_loop = mix["kind"] == "closed"

    def in_window(r):
        if closed_loop:           # alive at some point of the window
            last = r["token_t"][-1] if r["token_t"] else r["sent"]
            return r["sent"] < w["t_close"] and (r["cut"] or last >= w["t_open"])
        return w["t_open"] <= r["due"] < w["t_close"]

    def failed(r):
        if r["cut"]:
            # the closed loop is stopped mid-flight by design; an open-loop
            # request that outlived the drain was not answered
            return not closed_loop
        if r["code"] != 200 or r["error"]:
            return True
        return len(r["token_t"]) != r["max_tokens"] or r["finish"] != "length"

    mine = [r for r in requests if in_window(r)]
    n_failed = sum(1 for r in mine if failed(r))
    win_steps = [s for s in steps if w["t_open"] <= s["t1"] < w["t_close"]]
    delta = {k: snaps["close"][k] - snaps["open"][k] for k in snaps["open"]
             if k != "t"} if "close" in snaps else {}
    window_compiles = delta.get("compiles", 0) + delta.get("xla_compiles", 0)
    tok_in = sum(1 for r in requests for t in r["token_t"]
                 if w["t_open"] <= t < w["t_close"])
    started = [r for r in requests if w["t_open"] <= r["sent"] < w["t_close"]]
    finished = [r for r in requests if r["token_t"] and not r["cut"]
                and w["t_open"] <= r["token_t"][-1] < w["t_close"]]
    # a guarded device call that outlives the engine's watchdog is retried
    # (inference/failures.py): every request is still answered, so the run
    # stays correct, and the seconds without a step are in its metrics
    ends = [w["t_open"]] + [s["t1"] for s in win_steps] + [w["t_close"]]
    note("window", seconds=w["seconds"], requests_in_window=len(mine),
         failed=n_failed, step_retries=delta.get("step_retries", 0),
         longest_gap_between_steps_s=max(b - a for a, b in zip(ends, ends[1:])),
         started=len(started), finished=len(finished),
         prompt_tokens_started=sum(r["n_prompt"] for r in started),
         output_tokens=tok_in, engine=delta, steps=len(win_steps),
         tokens_per_step=(sum(s["n_tokens"] for s in win_steps)
                          / max(1, len(win_steps))),
         decode_seqs_per_step=(sum(s["emitted"] for s in win_steps)
                               / max(1, len(win_steps))),
         mean_context=(sum(s["ctx_tokens"] for s in win_steps)
                       / max(1, sum(s["n_seqs"] for s in win_steps))),
         generator={k: done[k] for k in ("requests", "late_ms_max",
                                         "late_ms_mean", "multisets")},
         races={k: min(r, key=r.get) for k, r in eng.probe_times.items()},
         records=ctx["records_path"])

    rec = {
        "kind": "serve", "setup_s": setup_s, "window": w, "t_end": t_end,
        "requests": requests, "requests_in_window": mine, "steps": steps,
        "window_steps": win_steps, "engine_delta": delta,
        "engine_requests": {r["uid"]: r for r in engine_requests},
        "window_compiles": window_compiles,
        "trace_dir": ctx["trace_dir"] if args.trace else None,
        "trace_window": trace_window,
        "compared": compared, "attempted": len(mine), "failed": n_failed,
        "generator": done, "device": device,
        "races": dict(eng.probe_times),
    }
    rec["correct"] = bool(all(c["ok"] for c in compared.values())
                          and n_failed == 0 and window_compiles == 0
                          and len(mine) > 0)
    return rec
