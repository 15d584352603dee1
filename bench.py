"""Benchmark: GPT-2-small causal-LM training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu"}.

Baseline: BASELINE.json config 1 ("HF GPT-2-small, ZeRO-1, single host").
The reference publishes no single-chip GPT-2 tokens/sec number, so
vs_baseline is computed against model-FLOPs utilisation: reference Ulysses
sustains >54% of peak on A100s (blogs/deepspeed-ulysses/README.md:82);
we report achieved MFU / 0.54 as the ratio.
"""

import json
import sys
import time

import numpy as np


def main(trace_path=None, profile_dir=None):
    """``trace_path``: export a Chrome trace (Perfetto-loadable) of the
    pipelined serving leg's depth-2 run (``--trace out.json``).
    ``profile_dir``: additionally arm a deep-capture window on that leg
    and emit a MERGED host+device timeline via tools/tracemerge.py
    (``--profile out/``)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    import jax

    dev = jax.devices()[0]      # raises when the backend cannot start
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX reports "
                         f"{dev.platform!r} (a CPU timing is not a result)")
    on_tpu = True

    seq = 1024 if on_tpu else 128
    batch = 32 if on_tpu else 2
    model = build_model("gpt2", max_seq_len=seq, remat=False,
                        attention_impl="xla_flash",
                        **({} if on_tpu else
                           dict(num_layers=2, d_model=128, num_heads=4,
                                vocab_size=1024)))
    cfg = model.config
    config = {
        "train_micro_batch_size_per_device": batch,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": -1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        # device telemetry (docs/OBSERVABILITY.md): per-program
        # cost_analysis + memory gauges embedded in train_metrics —
        # the probe's duplicate compile lands in the warmup, outside
        # every timed window
        "telemetry": {"device": True},
    }
    engine = ds.initialize(model=model, config=config)
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  PrefetchingLoader,
                                                  synthetic_lm_data)

    n = 10 if on_tpu else 3
    windows = 3 if on_tpu else 1
    data = synthetic_lm_data(cfg.vocab_size,
                             engine.train_batch_size * (n * windows + 4),
                             seq)
    loader = PrefetchingLoader(
        DataLoader(data, engine.train_batch_size), engine)
    it = iter(loader)
    for _ in range(2):                      # compile + steady state
        m = engine.train_batch(next(it))
    float(m["loss"])                        # drain warmup before timing
    engine.metrics.reset()                  # telemetry covers the timed
    #                                         window only, not the compile
    # median of several windows; each window ends with a host fetch of
    # a step-output scalar.  block_until_ready is a real completion
    # barrier on the chip too (chip_smoke.py measures it every run);
    # the fetch costs a further ~2 ms per window
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        m = None
        for _ in range(n):
            m = engine.train_batch(next(it))
        float(m["loss"])
        rates.append(time.perf_counter() - t0)
    dt = sorted(rates)[len(rates) // 2]

    tokens_per_step = engine.train_batch_size * (seq - 1)
    tok_s = n * tokens_per_step / dt
    # host-phase telemetry of the timed window (docs/OBSERVABILITY.md):
    # per-phase ms counters + the host-wall histogram summary
    train_metrics = engine.metrics_snapshot()
    # compiler/device view: train-step cost_analysis + memory poll
    train_device = engine.devtel.snapshot() if engine.devtel else None

    # model FLOPs: 6 * n_params * tokens (fwd+bwd), attention extra term
    from deepspeed_tpu.runtime import param_count
    n_params = param_count(model.params)
    attn_flops = 12 * cfg.num_layers * cfg.d_model * (seq - 1)  # per token
    flops_per_token = 6 * n_params + attn_flops
    achieved = tok_s * flops_per_token
    # bf16 peak per chip: the one table, keyed by device_kind
    from deepspeed_tpu.telemetry.device import peak_flops
    peak = peak_flops(dev)
    if peak is None:
        raise RuntimeError(f"bench: no published peak for device_kind "
                           f"{dev.device_kind!r} in telemetry/device.py")
    mfu = achieved / peak
    vs_baseline = mfu / 0.54 if on_tpu else 0.0

    # free each leg's HBM before the next: the engines' donated state and
    # compiled executables stay alive through main()'s locals otherwise
    # (the llama train leg OOMed behind the GPT-2 engine's 2.5 GB)
    import gc
    import traceback
    del engine, loader, it, data, model

    # a leg that raises still lets the others run (one OOM must not
    # cost the whole capture), but the process then exits non-zero
    failed = []

    def leg(fn, *a):
        gc.collect()
        try:
            return fn(*a)
        except Exception as e:
            traceback.print_exc()
            name = getattr(fn, "__name__", "leg")
            failed.append(name)
            return {f"{name}_error": f"{type(e).__name__}: "
                    f"{(str(e).splitlines() or [''])[0][:120]}"}

    serve = leg(serving_bench, on_tpu)
    pipe = leg(pipeline_serving_bench, on_tpu, trace_path, profile_dir)
    prefix = leg(shared_prefix_serving_bench, on_tpu)
    spec = leg(spec_decode_serving_bench, on_tpu)
    overload = leg(overload_serving_bench, on_tpu)
    chaos = leg(chaos_serving_bench, on_tpu)
    fleet = leg(fleet_serving_bench, on_tpu)
    tiered = leg(tiered_kv_serving_bench, on_tpu)
    disagg = leg(disagg_serving_bench, on_tpu)
    autoscale = leg(autoscale_serving_bench, on_tpu)
    http = leg(http_serving_bench, on_tpu)
    llama_train = leg(llama_train_bench, on_tpu, peak)
    llama_serve = leg(llama8b_serving_bench, on_tpu)
    moe = leg(moe_train_bench, on_tpu, peak)
    comm = leg(comm_overlap_bench, on_tpu)

    out = {
        "metric": "gpt2s_train_tokens_per_sec_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "vs_baseline": round(vs_baseline, 4),
        "mfu": round(mfu, 4) if on_tpu else 0.0,
        # engine version + a digest of the benchmark-relevant config
        # DEFAULTS: successive captures are only comparable when
        # these match — a PR that changes a default shifts every leg,
        # and the hash makes that visible instead of silently skewing
        # the trajectory (bench_fingerprint())
        **bench_fingerprint(),
        "train_metrics": train_metrics,
        "train_device_metrics": train_device,
    }
    out.update(serve)
    print(json.dumps({**out, **pipe, **prefix, **spec, **overload,  # tpulint: disable=print — the bench's one JSON output line
                      **chaos, **fleet, **tiered, **disagg, **autoscale,
                      **http, **llama_train,
                      **llama_serve, **moe, **comm}))
    if failed:
        sys.exit(f"bench: legs failed: {failed}")


def bench_fingerprint():
    """Version + config-default fingerprint recorded in every BENCH
    JSON capture: ``engine_version`` and a short digest over the
    serving/overload/failure config defaults (the knobs whose defaults
    PRs keep evolving — pipeline depth, donation, prefix cache, spec
    decode, shed policy, watchdog...).  Two BENCH files with different
    hashes measured different default engines; compare legs only
    within a hash — which is exactly how ``tools/benchdiff.py`` gates:
    matching hash => hard per-leg thresholds, changed hash =>
    report-only.  ONE implementation, shared with the flight
    recorder's post-mortems (telemetry/flight.py), so BENCH captures
    and black-box dumps join on the same key."""
    from deepspeed_tpu.telemetry import config_fingerprint

    return config_fingerprint()


def comm_overlap_bench(on_tpu: bool):
    """Overlapped-vs-serial collective microbench (T3 arxiv 2401.16677
    tile decomposition + EQuARX arxiv 2506.17615 quantized wire;
    docs/SERVING.md "Overlapped & quantized collectives").

    Four comm plans over the same row-parallel GEMM: serial psum,
    tile-decomposed psum (bitwise-exact), ppermute ring, int8 quantized
    wire — numerics cross-checked inside the leg before timing.  With
    several local devices it measures the actual fabric in-process.
    With ONE local device it runs in a child process forced to the CPU
    (an 8-device virtual mesh), so the child never asks for the chip
    this process holds — but what then lands under ``comm_*_ms`` /
    ``comm_*_speedup`` is a CPU-emulation timing, not a device metric.
    Whether that leg stays is the benchmark PR's call (ROADMAP S1/S6).
    The metrics land top-level in the BENCH JSON, where
    ``tools/benchdiff.py``'s direction rules read them."""
    import os
    import subprocess

    import jax

    if len(jax.devices()) > 1:
        from deepspeed_tpu.comm.bench import overlap_bench

        rec = overlap_bench(trials=10, warmups=3)
    else:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=8")
        if not any("concurrency_optimized_scheduler" in f for f in flags):
            flags.append(
                "--xla_cpu_enable_concurrency_optimized_scheduler=false")
        env["XLA_FLAGS"] = " ".join(flags)
        here = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.comm.bench",
             "--overlap", "--trials", "10"],
            capture_output=True, text=True, env=env, check=True, cwd=here)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    return {"comm_overlap_bench": rec,
            "comm_serial_ms": rec["comm_serial_ms"],
            "comm_overlapped_ms": rec["comm_overlapped_ms"],
            "comm_overlap_speedup": rec["comm_overlap_speedup"],
            "comm_quant_speedup": rec["comm_quant_speedup"]}


def chaos_serving_bench(on_tpu: bool):
    """Fault-tolerance leg (docs/SERVING.md "Failure domains &
    recovery"): the loadgen chaos smoke — injected crash + watchdog
    expiry + a uid-targeted poison request + a mid-traffic
    snapshot/restore warm restart, across greedy/seeded sampling and
    prefix cache on/off — run as a bench capture.  The acceptance
    asserts run inside (never deadlocks, never leaks, exactly one
    terminal status each, unaffected requests token-identical to a
    fault-free run); the JSON records the per-variant recovery
    telemetry (retries, failed, restarts, steps)."""
    from tools.loadgen import chaos_smoke

    out = chaos_smoke(seed=0)
    return {"chaos_serving": {
        "ok": out["ok"],
        "variants": out["variants"],
    }}


def fleet_serving_bench(on_tpu: bool):
    """Replica-fleet leg (docs/SERVING.md "Fleet: routing, failover,
    migration"): the loadgen fleet sweep — one shared-prefix workload
    through 1 replica, then a 3-replica fleet under cache-affinity
    placement with a mid-sweep replica KILL, then the same fleet under
    round-robin (the affinity bar's baseline).  The headline metrics
    land top-level so ``tools/benchdiff.py``'s existing direction
    rules gate them: ``*_goodput_tok_s`` / ``*_hit_rate`` up-is-better,
    ``*_ttft_*_ms`` down-is-better.  The affinity acceptance bar —
    cache-affinity placement beats round-robin's measured prefix hit
    rate on this workload — is asserted by tests/test_router.py; the
    JSON records the margin."""
    from tools.loadgen import fleet_bench

    out = fleet_bench(seed=0)
    return {"fleet_serving": out,
            "fleet_goodput_tok_s": out["affinity"]["goodput_tok_s"],
            "fleet_single_goodput_tok_s": out["single"]["goodput_tok_s"],
            "fleet_affinity_hit_rate": out["affinity"]["hit_rate"],
            "fleet_round_robin_hit_rate": out["round_robin"]["hit_rate"],
            "fleet_ttft_p95_prekill_ms":
                out["affinity"]["ttft_p95_prekill_ms"],
            "fleet_ttft_p95_postkill_ms":
                out["affinity"]["ttft_p95_postkill_ms"],
            # fleet observability diagnostics (docs/OBSERVABILITY.md
            # "Fleet observability"): fleet + per-replica anomaly
            # tallies (benchdiff REPORTS their deltas, never gates)
            # and the aggregated fleet device metrics
            "fleet_serving_anomalies": out["affinity"]["anomalies"],
            "fleet_device_metrics": out["affinity"]["device_metrics"]}


def tiered_kv_serving_bench(on_tpu: bool):
    """Tiered-KV leg (docs/KV_TIERING.md): a revisit-heavy prefix
    workload whose working set is >4x the KV pool, through
    discard-on-evict / tiered / all-HBM arms at identical shapes, plus
    the fleet remote-restage-vs-re-prefill arm.  Token parity across
    arms and tier-counter consistency (revives never outrun demotions,
    zero verify failures) are asserted inside before anything is
    recorded.  The headline metrics land top-level for
    ``tools/benchdiff.py``'s direction rules: ``tiered_kv_hit_rate``
    up-is-better, the ``*_ttft_*`` keys down-is-better — including
    ``tiered_kv_ttft_vs_allhbm``, the 1.25x acceptance bar (tiered p95
    TTFT over the all-HBM ceiling) — and
    ``tiered_kv_remote_restage_speedup`` (re-prefill TTFT over
    cross-replica restage TTFT) up-is-better."""
    from tools.loadgen import tiered_kv_bench

    out = tiered_kv_bench(seed=0)
    return {"tiered_kv": out,
            "tiered_kv_hit_rate": out["tiered"]["hit_rate"],
            "tiered_kv_ttft_p95_ms": out["tiered"]["ttft_ms_p95"],
            "tiered_kv_baseline_ttft_p95_ms":
                out["baseline"]["ttft_ms_p95"],
            "tiered_kv_allhbm_ttft_p95_ms": out["allhbm"]["ttft_ms_p95"],
            "tiered_kv_ttft_vs_allhbm": out["ttft_vs_allhbm"],
            "tiered_kv_remote_restage_speedup":
                out["remote_restage_speedup"]}


def disagg_serving_bench(on_tpu: bool):
    """Disaggregation leg (docs/SERVING.md "Disaggregated pools &
    elasticity"): ONE seeded mixed-SLO trace through a 3-mixed-replica
    colocated fleet (chunked prefill — the strongest colocated
    baseline) and a 2-prefill + 1-decode disaggregated fleet at EQUAL
    replica count.  The headline metrics land top-level so
    ``tools/benchdiff.py``'s existing direction rules gate them:
    ``disagg_interactive_speedup`` (colocated p95 TTFT rounds over
    disaggregated — the acceptance bar is > 1.0: pools win at
    identical hardware) up-is-better, the ``disagg_*_ttft_*_ms`` pair
    down-is-better, ``disagg_goodput_tok_s`` up-is-better."""
    from tools.loadgen import disagg_bench

    out = disagg_bench(seed=0)
    return {"disagg_serving": out,
            "disagg_interactive_speedup":
                out["disagg_interactive_speedup"],
            "disagg_ttft_p95_interactive_ms":
                out["disagg"]["ttft_p95_interactive_ms"],
            "disagg_colocated_ttft_p95_interactive_ms":
                out["colocated"]["ttft_p95_interactive_ms"],
            "disagg_goodput_tok_s": out["disagg"]["goodput_tok_s"],
            "disagg_colocated_goodput_tok_s":
                out["colocated"]["goodput_tok_s"]}


def autoscale_serving_bench(on_tpu: bool):
    """Elasticity leg (docs/SERVING.md "Disaggregated pools &
    elasticity"): the loadgen scaling chaos smoke — a seeded load
    swing through a disaggregated fleet with the signal-driven
    actuator attached — run as a bench capture.  The acceptance
    asserts run inside (pool scales up AND back down, zero lost
    requests, exact token parity, handoff journeys); the JSON records
    the decision log and swing telemetry."""
    from tools.loadgen import scale_chaos_smoke

    out = scale_chaos_smoke(seed=0)
    return {"autoscale_serving": {
        "ok": out["ok"],
        "variants": out["variants"],
    }}


def http_serving_bench(on_tpu: bool):
    """Sockets-to-tokens leg (docs/SERVING.md "Network gateway"): the
    same seeded bursty trace through the in-process ``replay`` driver
    and through real loopback sockets against a spawned gateway, with
    token parity asserted inside before anything is recorded.  The
    headline metrics land top-level so ``tools/benchdiff.py``'s
    existing direction rules gate them: ``http_goodput_tok_s`` /
    ``inproc_goodput_tok_s`` up-is-better, ``http_ttft_p95_ms`` /
    ``inproc_ttft_p95_ms`` down-is-better, and the measured wire
    overhead ``http_ttft_overhead_ratio`` (client-wall p95 over
    in-process engine-record p95) is gated down-is-better too — a PR
    that makes the gateway slower relative to the engine fails the
    same-config compare even when both got faster in absolute terms."""
    from tools.loadgen import http_bench

    out = http_bench(seed=0)
    return {"http_serving": out,
            "http_goodput_tok_s": out["http_goodput_tok_s"],
            "inproc_goodput_tok_s": out["inproc_goodput_tok_s"],
            "http_ttft_p95_ms": out["http_ttft_p95_ms"],
            "inproc_ttft_p95_ms": out["inproc_ttft_p95_ms"],
            "http_ttft_overhead_ratio": out["http_ttft_overhead_ratio"]}


def moe_train_bench(on_tpu: bool, peak: float):
    """8-expert MoE training on one chip (BASELINE config 4 is Mixtral
    EP x SP; EP multichip correctness is witnessed by the driver dryrun's
    expert=2 leg — this leg gives MoE its real-TPU perf signal).  Times
    BOTH dispatch modes at the same shapes: 'ragged' (dropless
    lax.ragged_dot grouped GEMM, parallel/moe.py:215 megablox analog) vs
    'scatter' (capacity-bounded index dispatch)."""
    import gc
    import time

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime import param_count
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  PrefetchingLoader,
                                                  synthetic_lm_data)

    seq = 1024 if on_tpu else 128
    batch = 8 if on_tpu else 2
    out = {}
    for mode in ("ragged", "scatter"):
        model = build_model(
            "gpt2", max_seq_len=seq, num_experts=8, moe_top_k=2,
            moe_dispatch=mode,
            **(dict(num_layers=6, d_model=768, num_heads=12,
                    remat=False,
                    attention_impl="xla_flash") if on_tpu else
               dict(num_layers=2, d_model=128, num_heads=4,
                    vocab_size=1024)))
        cfg = model.config
        engine = ds.initialize(model=model, config={
            "train_micro_batch_size_per_device": batch,
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": -1},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
            "telemetry": {"device": True},
        })
        data = synthetic_lm_data(cfg.vocab_size,
                                 engine.train_batch_size * 12, seq)
        loader = PrefetchingLoader(
            DataLoader(data, engine.train_batch_size), engine)
        it = iter(loader)
        for _ in range(2):
            m = engine.train_batch(next(it))
        float(m["loss"])
        engine.metrics.reset()              # exclude compile from telemetry
        n = 5 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(n):
            m = engine.train_batch(next(it))
        float(m["loss"])
        dt = time.perf_counter() - t0
        tok_s = n * engine.train_batch_size * (seq - 1) / dt
        if mode == "ragged":
            # active-param MFU: top-k of num_experts per token
            n_params = param_count(model.params)
            expert_params = param_count(model.params["blocks"]["experts"])
            active = n_params - expert_params \
                * (cfg.num_experts - cfg.moe_top_k) // cfg.num_experts
            fpt = 6 * active + 12 * cfg.num_layers * cfg.d_model * (seq - 1)
            out["moe8x_train_mfu_active"] = round(
                tok_s * fpt / peak, 4) if on_tpu else 0.0
        out[f"moe8x_train_tok_s_{mode}"] = round(tok_s, 1)
        out[f"moe8x_train_metrics_{mode}"] = engine.metrics_snapshot()
        out[f"moe8x_train_device_metrics_{mode}"] = \
            engine.devtel.snapshot() if engine.devtel else None
        del engine, loader, it, data, model
        gc.collect()
    return out


def llama_train_bench(on_tpu: bool, peak: float):
    """Llama-architecture training on one chip (BASELINE configs 2-3 are
    llama-class): ~0.7B llama (RoPE/GQA/SwiGLU/RMSNorm, seq 2048) under
    ZeRO-3, without optimizer offload (not measured on the chip)."""
    import time

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime import param_count
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  PrefetchingLoader,
                                                  synthetic_lm_data)

    seq = 2048 if on_tpu else 128
    batch = 2 if on_tpu else 2
    model = build_model(
        "llama-tiny",
        **(dict(vocab_size=32000, num_layers=12, d_model=2048,
                num_heads=16, num_kv_heads=8, d_ff=5504, max_seq_len=seq,
                remat=True, remat_policy="xla_flash",
                attention_impl="xla_flash") if on_tpu else
           dict(vocab_size=512, num_layers=2, d_model=128, num_heads=4,
                num_kv_heads=2, d_ff=352, max_seq_len=seq)))
    cfg = model.config
    engine = ds.initialize(model=model, config={
        "train_micro_batch_size_per_device": batch,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "mesh": {"data": -1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "telemetry": {"device": True},
    })
    data = synthetic_lm_data(cfg.vocab_size,
                             engine.train_batch_size * 16, seq)
    loader = PrefetchingLoader(
        DataLoader(data, engine.train_batch_size), engine)
    it = iter(loader)
    for _ in range(2):
        m = engine.train_batch(next(it))
    float(m["loss"])
    engine.metrics.reset()                  # exclude compile from telemetry
    n = 5 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(n):
        m = engine.train_batch(next(it))
    float(m["loss"])
    dt = time.perf_counter() - t0
    tok_s = n * engine.train_batch_size * (seq - 1) / dt
    n_params = param_count(model.params)
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.d_model \
        * (seq - 1)
    mfu = tok_s * flops_per_token / peak if on_tpu else 0.0
    return {
        "llama07b_train_tok_s": round(tok_s, 1),
        "llama07b_train_mfu": round(mfu, 4),
        "llama07b_train_metrics": engine.metrics_snapshot(),
        "llama07b_train_device_metrics":
            engine.devtel.snapshot() if engine.devtel else None,
    }


def _synthetic_int8_llama(cfg):
    """Build (dense_remainder, quant_tree) for a llama config DIRECTLY in
    the quantized representation — no fp32 init, no host-side
    quantization pass (what a quantized-checkpoint loader would produce;
    this bench measures serving throughput, not model quality).  Arrays
    are tile-filled (memcpy speed) and device_put once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.ops.quant import QuantizedTensor

    shapes = jax.eval_shape(lambda k: init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    tile_i8 = np.frombuffer(np.random.RandomState(0).bytes(1 << 20),
                            np.int8)
    tile_f = (np.frombuffer(np.random.RandomState(1).bytes(1 << 22),
                            np.uint8).astype(np.float32) - 127.5) / 2900.0

    def fill_i8(shape):
        n = int(np.prod(shape))
        return jax.device_put(np.resize(tile_i8, n).reshape(shape))

    def fill_f(shape, dtype=jnp.bfloat16):
        n = int(np.prod(shape))
        return jax.device_put(
            np.resize(tile_f, n).reshape(shape).astype(dtype))

    quantizable = ("wq", "wk", "wv", "wo", "wi", "wg")

    def build(tree):
        out = {}
        for name, sub in tree.items():
            if isinstance(sub, dict):
                out[name] = build(sub)
            else:
                out[name] = (jnp.ones(sub.shape, jnp.bfloat16)
                             if name in ("scale", "bias")
                             else fill_f(sub.shape))
        return out

    dense = {}
    quant = {"blocks": {}}
    for top, sub in shapes.items():
        if top == "blocks":
            dense["blocks"] = {}
            for gname, grp in sub.items():
                dgrp, qgrp = {}, {}
                for name, sds in grp.items():
                    if name in quantizable and len(sds.shape) >= 3:
                        # row-wise weight-shaped int8 (see
                        # quant.quantize_rowwise): dequant fuses into the
                        # matmul, no grouped-flat relayout
                        L, d0 = sds.shape[0], sds.shape[1]
                        sc = (L, d0) + (1,) * (len(sds.shape) - 2)
                        qgrp[name] = QuantizedTensor(
                            fill_i8(sds.shape),
                            jax.device_put(np.full(sc, 0.004, np.float32)),
                            None, 8, tuple(sds.shape), jnp.bfloat16)
                    else:
                        dgrp[name] = (jnp.ones(sds.shape, jnp.bfloat16)
                                      if "ln" in gname
                                      else fill_f(sds.shape))
                dense["blocks"][gname] = dgrp
                if qgrp:
                    quant["blocks"][gname] = qgrp
        elif top == "embed":
            tab = sub["table"]
            quant["embed"] = {"table": QuantizedTensor(
                fill_i8(tab.shape),
                jax.device_put(np.full((tab.shape[0], 1), 0.004,
                                       np.float32)),
                None, 8, tuple(tab.shape), jnp.bfloat16)}
            dense["embed"] = {}
        else:
            dense[top] = build(sub)
    return dense, quant


def llama8b_serving_bench(on_tpu: bool):
    """ZeRO-Inference serving of Llama-3-8B int8 on ONE chip — the
    llama-class serving leg the reference headlines (FastGen README:133
    SLA-style numbers: prompt tok/s + per-token generation latency EMA).

    The dense model (16 GB bf16) cannot materialize anywhere on this
    rig's budget: the engine is built PRE-QUANTIZED
    (``InferenceEngine(..., quant_tree=...)`` — the quantized-checkpoint
    flow) so only int8 payloads ever exist, and the quant tree rides the
    step as jit ARGUMENTS (a closure capture baked 7.5 GB of constants
    into the HLO and killed the remote compile — measured 2026-07-30)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models.presets import PRESETS
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    n_seqs, prompt_len = (8, 512) if on_tpu else (2, 8)
    decode_rounds = 4 if on_tpu else 2

    preset = dict(PRESETS["llama3-8b" if on_tpu else "llama-tiny"])
    preset["max_seq_len"] = 2048
    if not on_tpu:
        preset.update(vocab_size=512, num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=352)
    cfg = TransformerConfig(**preset)
    dense, quant = _synthetic_int8_llama(cfg)
    model = Model.from_params(cfg, dense)
    # budget 1024 = two 512-token prompts per step: each full-model
    # weight pass amortizes over 2x the prompt tokens (prompt 1761 ->
    # 2189 tok/s measured; budget 2048 OOMs the 8B compile)
    # int8 paged KV (per-vector scales): halves the KV HBM stream that
    # competes with the int8 weights for decode bandwidth at long context
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=1024 if on_tpu else 16, max_seqs=n_seqs,
        kv_block_size=64 if on_tpu else 16,
        num_kv_blocks=128 if on_tpu else 32,
        kv_quant="int8",
        decode_burst=8 if on_tpu else 2,
        device_telemetry="on"), quant_tree=quant)

    r = np.random.RandomState(0)
    vocab = model.config.vocab_size
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)

    # warm compile caches (prompt-sized bucket) outside the timed region
    eng.put(-1, list(r.randint(0, vocab, prompt_len)))
    while eng.step(sampling=sp).get(-1) is None:
        pass
    eng.flush(-1)
    eng.reset_metrics()     # warmup compile must not contaminate the
    #                         reported request-lifecycle aggregate

    # --- prefill: prompt throughput + TTFT
    for uid in range(n_seqs):
        eng.put(uid, list(r.randint(0, vocab, prompt_len)))
    t0 = time.perf_counter()
    ttft = {}
    while len(ttft) < n_seqs:
        out = eng.step(sampling=sp)
        now = time.perf_counter() - t0
        for uid in out:
            ttft.setdefault(uid, now * 1e3)
    prefill_dt = time.perf_counter() - t0
    prompt_tok_s = n_seqs * prompt_len / prefill_dt
    ttft_p50 = float(np.median(list(ttft.values())))

    # --- decode: device-side bursts; per-token latency EMA (FastGen's
    # generation SLA is an exponential moving average per token)
    for uid in range(n_seqs):
        eng.put(uid, [1])
    out = eng.decode_burst(sampling=sp)          # compile + settle
    for uid in out:
        eng.put(uid, [out[uid][-1]])
    # second settle: the first burst pushes context past a power-of-two
    # bucket boundary, recompiling the NEXT burst — that compile must not
    # land inside the timed region (it seeded a 245 ms/token EMA once)
    out = eng.decode_burst(sampling=sp)
    produced = 0
    ema = None
    t0 = time.perf_counter()
    t_last = t0
    for _ in range(decode_rounds):
        for uid in out:
            eng.put(uid, [out[uid][-1]])
        out = eng.decode_burst(sampling=sp)
        now = time.perf_counter()
        toks = sum(len(v) for v in out.values())
        per_tok_ms = (now - t_last) / max(toks // n_seqs, 1) * 1e3
        ema = per_tok_ms if ema is None else 0.9 * ema + 0.1 * per_tok_ms
        t_last = now
        produced += toks
    decode_tok_s = produced / (t_last - t0)
    name = "llama8b_int8" if on_tpu else "llama_tiny_int8"
    for uid in list(out):
        eng.flush(uid)
    sla = sla_goodput_sweep(eng, on_tpu, prompt_len)
    return {
        f"{name}_prompt_tok_s": round(prompt_tok_s, 1),
        f"{name}_ttft_p50_ms": round(ttft_p50, 1),
        f"{name}_decode_tok_s": round(decode_tok_s, 1),
        f"{name}_decode_ms_per_tok_ema": round(ema, 2),
        f"{name}_request_metrics": eng.request_metrics()["aggregate"],
        # the 8B leg is where utilization matters most: the burst
        # program's cost_analysis prices the int8 weight stream the
        # decode floor argument is built on (tools/profile_decode8b.py
        # reads the same numbers)
        f"{name}_device_metrics": eng.device_snapshot(),
        **{f"{name}_{k}": v for k, v in sla.items()},
    }


def sla_goodput_sweep(eng, on_tpu: bool, prompt_len: int):
    """FastGen-style SLA goodput curve (reference:
    blogs/deepspeed-fastgen/README.md:133-139 — 'effective throughput':
    QPS of requests meeting BOTH the prompt SLA (>=512 tok/s/seq, i.e.
    TTFT <= prompt_len/512 s) and a generation SLA tier (per-token EMA
    latency <= 1/2, 1/4, 1/6 s for the 2/4/6 tok/s tiers).

    Poisson arrivals at each swept rate drive the SplitFuse engine's
    continuous batching; per-request TTFT and inter-token gaps are
    measured at the step boundary (the scheduler's own granularity).
    Reports, per tier, the best observed goodput (met-SLA requests/sec)
    across the sweep."""
    import time

    import numpy as np

    from deepspeed_tpu.inference import SamplingParams

    gen_tokens = 32 if on_tpu else 4
    n_req = 16 if on_tpu else 4
    rates = (0.5, 1.0, 2.0, 4.0) if on_tpu else (8.0,)
    tiers = {"sla2": 0.5, "sla4": 0.25, "sla6": 1.0 / 6.0}
    ttft_limit = prompt_len / 512.0
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    r = np.random.RandomState(7)
    vocab = eng.cfg.vocab_size
    best = {k: 0.0 for k in tiers}
    curve = {}
    for rate in rates:
        arrivals = np.cumsum(r.exponential(1.0 / rate, n_req))
        reqs = {}          # uid -> dict(t_arrive, t_first, gaps, n)
        next_uid = 1000
        done = []
        t0 = time.perf_counter()
        def finish_tokens(uid, q, toks, t_step, n_new):
                if q["t_first"] is None:
                    q["t_first"] = t_step
                    n_new -= 1
                if n_new > 0:
                    gap = (t_step - q["t_last"]) / n_new
                    q["gaps"] += [gap] * n_new
                q["t_last"] = t_step
                q["n"] += len(toks) if isinstance(toks, list) else 1
                if q["n"] >= gen_tokens:
                    eng.flush(uid)
                    done.append((uid, q))
                    del reqs[uid]
                else:
                    last = toks[-1] if isinstance(toks, list) else toks
                    eng.put(uid, [int(last)])

        while len(done) < n_req:
            now = time.perf_counter() - t0
            while next_uid - 1000 < n_req and \
                    arrivals[next_uid - 1000] <= now:
                uid = next_uid
                eng.put(uid, list(r.randint(0, vocab, prompt_len)))
                reqs[uid] = {"t_arrive": arrivals[uid - 1000],
                             "t_first": None, "gaps": [], "n": 0,
                             "t_last": None}
                next_uid += 1
            if not reqs:
                if next_uid - 1000 >= n_req:
                    break               # everything arrived and finished
                time.sleep(min(0.01, max(0.0,
                               arrivals[next_uid - 1000] - now)))
                continue

            in_prefill = any(q["t_first"] is None for q in reqs.values())
            if not in_prefill and eng.icfg.decode_burst > 1:
                # decode-only phase: device-side bursts (the engine's
                # steady-state decode path; new arrivals re-enter the
                # SplitFuse step on the next loop iteration)
                out = eng.decode_burst(sampling=sp)
                t_step = time.perf_counter() - t0
                for uid, toks in out.items():
                    q = reqs.get(uid)
                    if q is not None:
                        finish_tokens(uid, q, list(toks), t_step,
                                      len(toks))
            else:
                out = eng.step(sampling=sp)
                t_step = time.perf_counter() - t0
                for uid, tok in out.items():
                    q = reqs.get(uid)
                    if q is not None:
                        finish_tokens(uid, q, int(tok), t_step, 1)
        elapsed = time.perf_counter() - t0
        for tier, limit in tiers.items():
            met = 0
            for uid, q in done:
                ttft = q["t_first"] - q["t_arrive"]
                ema = None
                for g in q["gaps"]:
                    ema = g if ema is None else 0.9 * ema + 0.1 * g
                if ttft <= ttft_limit and (ema or 0.0) <= limit:
                    met += 1
            goodput = met / elapsed
            best[tier] = max(best[tier], goodput)
            curve[f"r{rate}_{tier}"] = round(goodput, 3)
    return {**{f"goodput_qps_{k}": round(v, 3) for k, v in best.items()},
            "goodput_curve": curve}


def pipeline_serving_bench(on_tpu: bool, trace_path=None,
                           profile_dir=None):
    """Pipelined vs strict-sync serving loop at identical shapes: decode
    tokens/s for pipeline_depth 1 vs 2 plus the engine's per-step
    host-overhead breakdown (schedule / stage / device / readback ms)
    and the request-lifecycle aggregate (TTFT/TPOT histograms) of the
    timed run.  With ``trace_path``, the depth-2 leg runs with span
    tracing on and exports a Chrome trace of the timed region (open in
    Perfetto: one track per pipeline stage, the dispatch-ahead overlap
    visible directly).  With ``profile_dir`` (``--profile out/``), the
    depth-2 timed leg additionally arms a deep-capture window
    (telemetry/profiler.py) and emits a MERGED host+device timeline
    via tools/tracemerge.py — host stages and device/XLA activity on
    one Perfetto timeline, the ROADMAP-3 "track it before you can
    trigger it" bar.
    The pipeline's win is the host work it moves off the critical path:
    schedule+stage of step N+1 and the token readback of step N overlap
    step N/N+1's device compute, so the per-token host overhead
    (schedule+stage+readback) drops vs the synchronous baseline while
    outputs stay token-for-token identical."""
    import numpy as np

    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models import build_model

    n_seqs, prompt_len = (16, 64) if on_tpu else (8, 8)
    gen_tokens = 64 if on_tpu else 24
    model = build_model(
        "gpt2",
        **(dict(max_seq_len=1024) if on_tpu else
           dict(num_layers=2, d_model=128, num_heads=4, vocab_size=1024,
                max_seq_len=64)))
    r = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prompts = {uid: list(r.randint(0, vocab, prompt_len))
               for uid in range(n_seqs)}
    sp = SamplingParams(temperature=0.0, max_new_tokens=gen_tokens)

    out = {}
    breakdown = {}
    for depth in (1, 2):
        eng = InferenceEngine(model, InferenceConfig(
            token_budget=1024 if on_tpu else 64, max_seqs=n_seqs,
            kv_block_size=64 if on_tpu else 16,
            num_kv_blocks=1024 if on_tpu else 64,
            pipeline_depth=depth,
            trace=bool(trace_path) and depth == 2,
            device_telemetry="on", anomaly="on"))
        # warm the compile caches (probe + both context buckets) outside
        # the timed region
        eng.generate({u: list(p) for u, p in prompts.items()}, sp)
        # full telemetry reset: timings counters, request records, AND
        # the span ring, so every exported number covers the timed
        # region only
        eng.reset_metrics()
        if profile_dir and depth == 2:
            # deep capture over the head of the timed region: a
            # bounded jax.profiler window whose merged host+device
            # timeline shows the dispatch-ahead overlap for real
            eng.capture(steps=8, reason="bench_pipe2",
                        out_dir=profile_dir)
        t0 = time.perf_counter()
        toks = eng.generate({u: list(p) for u, p in prompts.items()}, sp)
        dt = time.perf_counter() - t0
        produced = sum(len(v) for v in toks.values())
        tl = eng.timings
        steps = max(tl["steps"], 1)
        out[f"pipe{depth}_decode_tok_s"] = round(produced / dt, 1)
        out[f"pipe{depth}_request_metrics"] = \
            eng.request_metrics()["aggregate"]
        out[f"pipe{depth}_device_metrics"] = eng.device_snapshot()
        out[f"pipe{depth}_anomalies"] = eng.anomaly_summary()
        if trace_path and depth == 2:
            out["trace_file"] = eng.tracer.export_chrome_trace(trace_path)
        if profile_dir and depth == 2 and eng.capture_dirs:
            from tools.tracemerge import merge_capture
            out["merged_trace_file"] = merge_capture(eng.capture_dirs[-1])
        breakdown[f"pipe{depth}"] = {
            "schedule_ms": round(tl["schedule_ms"] / steps, 3),
            "stage_ms": round(tl["stage_ms"] / steps, 3),
            "device_ms": round(tl["device_ms"] / steps, 3),
            "wait_ms": round(tl["wait_ms"] / steps, 3),
            "readback_ms": round(tl["readback_ms"] / steps, 3),
            "wall_ms_per_step": round(dt * 1e3 / steps, 3),
            "steps": tl["steps"],
        }
    # host overhead left ON THE CRITICAL PATH per step: wall minus the
    # device-busy time.  Device busy is taken from the strict-sync run
    # (same model/shapes, measured serially: its jit call + result wait
    # IS the device step, unperturbed by overlap) so both depths are
    # charged the same device cost and the difference is purely the
    # schedule/stage/readback work the pipeline hides behind compute.
    dev_busy = (breakdown["pipe1"]["device_ms"]
                + breakdown["pipe1"]["wait_ms"])
    for d in (1, 2):
        b = breakdown[f"pipe{d}"]
        b["host_crit_ms_per_step"] = round(
            max(0.0, b["wall_ms_per_step"] - dev_busy), 3)
    h1 = breakdown["pipe1"]["host_crit_ms_per_step"]
    h2 = breakdown["pipe2"]["host_crit_ms_per_step"]
    out["pipeline_host_overhead_ratio"] = round(h2 / h1, 3) if h1 else 0.0
    out["pipeline_step_breakdown_ms"] = breakdown
    return out


def shared_prefix_serving_bench(on_tpu: bool):
    """Prefix-cache serving leg: N requests sharing a 64-token system
    prompt (the few-shot/system-prompt traffic shape prefix caching
    targets), arriving one after another — each admitted after the
    previous request produced its first token, so later requests can
    alias the registered prompt blocks.  The token budget is set BELOW
    the prompt length: with SplitFuse's fixed-shape steps the cache's
    win is fewer prefill steps (a cache-hit request starts prefill at
    the first uncached token), which is both prefill-token throughput
    and TTFT.  Reports tok/s for prefix_cache on vs off at identical
    shapes, the speedup, and the engine's hit-rate counters."""
    import numpy as np

    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models import build_model

    n_req = 8
    shared_len = 64
    tail_len = 64 if on_tpu else 32
    budget = 64 if on_tpu else 32
    model = build_model(
        "gpt2",
        **(dict(max_seq_len=1024) if on_tpu else
           dict(num_layers=2, d_model=128, num_heads=4, vocab_size=1024,
                max_seq_len=256)))
    r = np.random.RandomState(0)
    vocab = model.config.vocab_size
    shared = list(r.randint(0, vocab, shared_len))
    prompts = {uid: shared + list(r.randint(0, vocab, tail_len))
               for uid in range(n_req)}
    sp = SamplingParams(temperature=0.0, max_new_tokens=1)
    out = {}
    for mode in ("off", "on"):
        # device telemetry on BOTH arms: the speedup must compare
        # engines differing in ONE knob (any probe cost lands
        # symmetrically, outside the timed region anyway)
        eng = InferenceEngine(model, InferenceConfig(
            token_budget=budget, max_seqs=4,
            kv_block_size=64 if on_tpu else 16,
            num_kv_blocks=64 if on_tpu else 48,
            prefix_cache=mode,
            device_telemetry="on", anomaly="on"))
        # warm the compile caches with an unrelated prompt (both modes
        # pay it; its blocks never match the shared prefix)
        eng.generate({-1: list(r.randint(0, vocab,
                                         shared_len + tail_len))}, sp)
        eng.reset_metrics()
        t0 = time.perf_counter()
        for uid, p in prompts.items():
            eng.generate({uid: list(p)}, sp)
        dt = time.perf_counter() - t0
        total_prompt = n_req * (shared_len + tail_len)
        out[f"shared_prefix_prefill_tok_s_{mode}"] = \
            round(total_prompt / dt, 1)
        if mode == "on":
            tm = eng.timings
            out["shared_prefix_cached_tokens"] = tm["cached_tokens"]
            out["shared_prefix_hit_rate"] = round(
                tm["cached_tokens"] / max(tm["prompt_tokens"], 1), 3)
            out["shared_prefix_request_metrics"] = \
                eng.request_metrics()["aggregate"]
            out["shared_prefix_device_metrics"] = eng.device_snapshot()
            out["shared_prefix_anomalies"] = eng.anomaly_summary()
    out["shared_prefix_speedup"] = round(
        out["shared_prefix_prefill_tok_s_on"]
        / max(out["shared_prefix_prefill_tok_s_off"], 1e-9), 2)
    return out


def spec_decode_serving_bench(on_tpu: bool):
    """Model-free speculative decoding leg (docs/SERVING.md
    "Speculative decoding"): decode throughput with ``spec_decode`` on
    vs off at identical shapes on the repetitive/code-like traffic
    prompt-lookup targets — each prompt is a short token motif repeated
    (the shape of templated code, quoted RAG context, or structured
    logs), and the decoded stream itself falls into cycles the n-gram
    proposer locks onto.  Outputs are token-identical by construction
    (the verify step is exact); the win is steps: an accepted window
    emits up to 1 + spec_max_draft tokens per dispatch.  Both modes run
    the strict-sync driver (pipeline_depth=1): a verify window's next
    fed token depends on host-side acceptance, so drafting rows cannot
    ride the depth-2 feedback marker anyway — speculation's natural
    home is the sync loop, where every saved step is pure wall-clock
    (measured here: depth-1 spec beats depth-2 spec, which trades each
    window for a pipeline bubble).  Reports decode tok/s both ways, the
    speedup, the acceptance_rate, and the mean accepted draft length —
    the measured signals ROADMAP item 4's autotuner needs to drive
    ``spec_decode="auto"`` from data."""
    import numpy as np

    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models import build_model

    n_seqs = 8 if on_tpu else 4
    prompt_len = 64 if on_tpu else 24
    gen_tokens = 96
    model = build_model(
        "gpt2",
        **(dict(max_seq_len=1024) if on_tpu else
           dict(num_layers=2, d_model=128, num_heads=4, vocab_size=1024,
                max_seq_len=256)))
    r = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prompts = {}
    for uid in range(n_seqs):
        motif = list(r.randint(0, vocab, 4 + uid % 3))
        reps = -(-prompt_len // len(motif))
        prompts[uid] = (motif * reps)[:prompt_len]
    sp = SamplingParams(temperature=0.0, max_new_tokens=gen_tokens)
    out = {}
    for mode in ("off", "on"):
        # device telemetry on BOTH arms — the on/off speedup must
        # isolate spec_decode, not spec_decode + telemetry
        eng = InferenceEngine(model, InferenceConfig(
            token_budget=256 if on_tpu else 64, max_seqs=n_seqs,
            kv_block_size=64 if on_tpu else 16,
            num_kv_blocks=256 if on_tpu else 96,
            pipeline_depth=1,
            spec_decode=mode, spec_max_draft=4,
            device_telemetry="on", anomaly="on"))
        # warm the compile caches; generate() flushes everything, so the
        # proposer history starts cold again for the timed run
        eng.generate({u: list(p) for u, p in prompts.items()}, sp)
        eng.reset_metrics()
        t0 = time.perf_counter()
        toks = eng.generate({u: list(p) for u, p in prompts.items()}, sp)
        dt = time.perf_counter() - t0
        produced = sum(len(v) for v in toks.values())
        out[f"spec_decode_tok_s_{mode}"] = round(produced / dt, 1)
        out[f"spec_decode_steps_{mode}"] = eng.timings["steps"]
        if mode == "on":
            tm = eng.timings
            out["spec_acceptance_rate"] = round(
                tm["spec_accepted_tokens"]
                / max(tm["spec_drafted_tokens"], 1), 3)
            out["spec_mean_accepted_draft_len"] = round(
                tm["spec_accepted_tokens"] / max(tm["spec_windows"], 1),
                3)
            out["spec_request_metrics"] = \
                eng.request_metrics()["aggregate"]
            out["spec_device_metrics"] = eng.device_snapshot()
            out["spec_anomalies"] = eng.anomaly_summary()
    out["spec_decode_speedup"] = round(
        out["spec_decode_tok_s_on"]
        / max(out["spec_decode_tok_s_off"], 1e-9), 2)
    return out


def overload_serving_bench(on_tpu: bool):
    """Overload-policy leg (docs/SERVING.md "Surviving overload"): the
    loadgen harness replays a seeded bursty trace at offered rates
    below and beyond capacity — with faults injected — and the SLO
    summaries (terminal-status mix, preemptions, TTFT/TPOT percentiles,
    deterministic step-indexed queue delays) land in the BENCH JSON as
    TTFT/TPOT-vs-load curves.  Every leg re-asserts token parity and
    the allocator partition; the replay raises rather than hangs if the
    engine wedges, so a scheduling regression fails the bench loudly."""
    from tools.loadgen import run_sweep

    qps = (2.0, 8.0, 32.0)
    sweep = run_sweep(qps, n_requests=24 if on_tpu else 16,
                      arrival="bursty", seed=0,
                      shed_policy="evict-lowest")
    curve = {str(q): {k: leg[k] for k in
                      ("statuses", "preemptions", "steps",
                       "ttft_ms_p50", "ttft_ms_p95",
                       "tpot_ms_p50", "tpot_ms_p95",
                       "ttft_steps_p95", "ttft_steps_hi_p95")}
             for q, leg in ((q, sweep["legs"][str(q)]) for q in qps)}
    return {"overload_slo_curve": curve,
            "overload_qps_axis": list(qps)}


def serving_bench(on_tpu: bool):
    """FastGen-style serving numbers (BASELINE.json metric: p50 TTFT +
    decode tok/s): 16 concurrent prompts of 128 tokens through the
    SplitFuse engine (token budget 256), then steady-state decode."""
    import numpy as np

    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models import build_model

    n_seqs, prompt_len = (32, 128) if on_tpu else (2, 8)
    model = build_model(
        "gpt2",
        **(dict(max_seq_len=1024) if on_tpu else
           dict(num_layers=2, d_model=128, num_heads=4, vocab_size=1024,
                max_seq_len=64)))
    # large prefill budget: on high-RTT links TTFT is dispatch-bound, so
    # fewer, bigger SplitFuse chunks win (599 vs 1678 ms p50 measured at
    # 1024 vs 256); decode latency is governed by the bursts, not the
    # prefill budget
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=1024 if on_tpu else 16, max_seqs=n_seqs,
        kv_block_size=64 if on_tpu else 16,
        num_kv_blocks=1024 if on_tpu else 32,
        decode_burst=8 if on_tpu else 2,
        device_telemetry="on", anomaly="on", slo="on"))
    r = np.random.RandomState(0)
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)
    vocab = model.config.vocab_size

    # warm the compile caches (probe + the prompt-sized context bucket)
    # outside the timed region
    eng.put(-1, list(r.randint(0, vocab, prompt_len)))
    while eng.step(sampling=sp).get(-1) is None:
        pass
    eng.flush(-1)
    eng.reset_metrics()     # the warmup request's compile-dominated TTFT
    #                         must not contaminate the reported aggregate

    # --- TTFT: enqueue all prompts, time each seq's first sampled token
    # (alternating SLO classes so the embedded scorecard is per-class)
    for uid in range(n_seqs):
        eng.put(uid, list(r.randint(0, vocab, prompt_len)),
                slo_class="interactive" if uid % 2 == 0 else "batch")
    t0 = time.perf_counter()
    ttft = {}
    while len(ttft) < n_seqs:
        out = eng.step(sampling=sp)
        now = time.perf_counter() - t0
        for uid in out:
            ttft.setdefault(uid, now * 1e3)
    ttft_p50_ms = float(np.median(list(ttft.values())))

    # --- steady-state decode throughput: all seqs live, device-side
    # decode bursts (K forwards per dispatch — the sampled token feeds
    # the next forward on-device)
    rounds = 6 if on_tpu else 2
    for uid in range(n_seqs):           # feed the sampled token back
        eng.put(uid, [1])
    out = eng.decode_burst(sampling=sp)          # compile + settle
    produced = 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        for uid in out:
            eng.put(uid, [out[uid][-1]])
        out = eng.decode_burst(sampling=sp)
        produced += sum(len(v) for v in out.values())
    dt = time.perf_counter() - t0
    # flush everything so per-request TPOT (observed at finish) lands in
    # the histograms, then report the leg's lifecycle aggregate
    for uid in range(n_seqs):
        eng.flush(uid)
    req = eng.request_metrics()["aggregate"]
    return {"serving_ttft_p50_ms": round(ttft_p50_ms, 1),
            "serving_decode_tok_s": round(produced / dt, 1),
            "serving_request_metrics": req,
            # device-telemetry capture (docs/OBSERVABILITY.md "Device &
            # compiler telemetry"): per-program cost_analysis, derived
            # MFU / HBM-bandwidth utilization over the timed window,
            # and peak memory_stats — the capture records utilization,
            # not just tok/s (absent fields = backend can't say)
            "serving_device_metrics": eng.device_snapshot(),
            # streaming-detector tally of the leg (anomaly counts are
            # report-only in benchdiff — a noisy rig fires latency
            # detectors without being a regression)
            "serving_anomalies": eng.anomaly_summary(),
            # per-class SLO scorecard (docs/OBSERVABILITY.md "SLOs &
            # error budgets"); benchdiff reports attainment/budget
            # deltas report-only, same policy as the anomaly counts
            "serving_slo": eng.slo_scorecard()}


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="export a Chrome trace (Perfetto-loadable) of "
                    "the pipelined serving leg's depth-2 timed run")
    ap.add_argument("--profile", metavar="OUT_DIR", default=None,
                    help="arm a deep-capture window on the depth-2 "
                    "timed leg and emit a merged host+device Perfetto "
                    "timeline (tools/tracemerge.py) under OUT_DIR")
    args = ap.parse_args()
    from deepspeed_tpu.platform.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(trace_path=args.trace, profile_dir=args.profile)
