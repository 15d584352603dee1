"""Per-fusion profile of the llama3-8b int8 DECODE burst (the decode
leg's 'weight-traffic-bound' claim needs a committed profile, as the
train step has).

Builds the exact bench engine (bench.py llama8b_serving_bench shapes)
WITH device telemetry on, runs warm decode bursts under the jax
profiler, and prints the top fusions by self-time with their
Compute/HBM bound_by attribution, plus the step-level accounting
(ms/burst, ms/token/seq) against the weight-read floor — the floor now
COMPUTED from the burst program's own ``cost_analysis`` bytes via the
engine's device telemetry (telemetry/device.py), not hand-written
constants.

Run on the chip (one process owns it):  python tools/profile_decode8b.py
Artifacts: chiprun_out/decode8b_trace (xplane),
chiprun_out/decode8b_hlo_stats.tsv
"""
# tpulint: disable-file=print — profiling CLI: the fusion table and
# step accounting ARE the tool's stdout deliverable

import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out")


def main():
    import jax

    from bench import _synthetic_int8_llama
    from deepspeed_tpu.platform.compile_cache import enable_compile_cache

    enable_compile_cache()
    from deepspeed_tpu.inference import (InferenceConfig, InferenceEngine,
                                         SamplingParams)
    from deepspeed_tpu.models.presets import PRESETS
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    on_tpu = jax.devices()[0].platform == "tpu"
    n_seqs, prompt_len = (8, 512) if on_tpu else (2, 8)
    preset = dict(PRESETS["llama3-8b" if on_tpu else "llama-tiny"])
    preset["max_seq_len"] = 2048
    if not on_tpu:
        preset.update(vocab_size=512, num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=352)
    cfg = TransformerConfig(**preset)
    dense, quant = _synthetic_int8_llama(cfg)
    model = Model.from_params(cfg, dense)
    eng = InferenceEngine(model, InferenceConfig(
        token_budget=1024 if on_tpu else 16, max_seqs=n_seqs,
        kv_block_size=64 if on_tpu else 16,
        num_kv_blocks=128 if on_tpu else 32,
        decode_burst=8 if on_tpu else 2,
        device_telemetry="on"), quant_tree=quant)

    r = np.random.RandomState(0)
    vocab = cfg.vocab_size
    sp = SamplingParams(temperature=0.0, max_new_tokens=1 << 30)

    # prompts in, prefill to steady decode state
    for uid in range(n_seqs):
        eng.put(uid, list(r.randint(0, vocab, prompt_len)))
    done = set()
    while len(done) < n_seqs:
        done.update(eng.step(sampling=sp).keys())

    for uid in range(n_seqs):
        eng.put(uid, [1])
    out = eng.decode_burst(sampling=sp)      # compile + settle
    for uid in out:
        eng.put(uid, [out[uid][-1]])
    out = eng.decode_burst(sampling=sp)      # warm

    # ---- timed + traced bursts -----------------------------------------
    # ONE profiler entry point (telemetry/profiler.py): the capture
    # window owns the jax.profiler session, the clock anchor, and the
    # loud absent-profiler degradation; each burst counts as one window
    # step, so `rounds` bursts complete it.  The same seam serves the
    # serving loop's anomaly-armed captures and bench --profile.
    trace_dir = os.path.join(OUT, "decode8b_trace")
    eng.capture(steps=3, reason="decode8b", out_dir=trace_dir)
    t0 = time.perf_counter()
    rounds = 3
    toks = 0
    for _ in range(rounds):
        for uid in out:
            eng.put(uid, [out[uid][-1]])
        out = eng.decode_burst(sampling=sp)
        toks += sum(len(v) for v in out.values())
    dt = time.perf_counter() - t0
    capture_dir = eng.capture_dirs[-1] if eng.capture_dirs else None
    merged = None
    if capture_dir:
        from tools.tracemerge import merge_capture
        merged = merge_capture(capture_dir)

    burst = eng.icfg.decode_burst
    per_tok_ms = dt / rounds / burst * 1e3
    # the floor, measured instead of asserted: the burst program's own
    # cost_analysis bytes over the chip's published HBM bandwidth
    # (device telemetry probed it at the burst's compile; the same
    # numbers land in the BENCH JSON's llama8b device_metrics)
    ds = eng.device_snapshot()
    burst_cost = next((c for k, c in ds["programs"].items()
                       if k.startswith("('b'")), {})
    bw = ds["peak_hbm_bw"]     # None for a device_kind not in the table
    floor_ms = burst_cost.get("bytes_accessed", 0) / bw * 1e3 if bw else 0
    print(json.dumps({
        "ms_per_burst": round(dt / rounds * 1e3, 1),
        "tokens_per_burst": toks // rounds,
        "ms_per_token_per_seq": round(per_tok_ms, 1),
        "decode_tok_s_aggregate": round(toks / dt, 1),
        "burst_flops": burst_cost.get("flops"),
        "burst_bytes_accessed": burst_cost.get("bytes_accessed"),
        "hbm_floor_ms_per_burst": round(floor_ms, 1) if floor_ms
        else None,
        "floor_ratio": round(dt / rounds * 1e3 / floor_ms, 2)
        if floor_ms else None,
        "mfu": ds["mfu"],
        "hbm_bw_util": ds["hbm_bw_util"],
        "memory": ds["memory"],
        "capture_dir": capture_dir,
        "merged_timeline": merged,
    }))

    # ---- hlo_stats dump -------------------------------------------------
    paths = sorted(glob.glob((capture_dir or trace_dir)
                             + "/**/*.xplane.pb", recursive=True))
    if not paths:
        print("no xplane captured (profiler absent on this "
              "backend/build, or CPU-only jaxlib) — the merged "
              "host-side timeline above is still written")
        return
    try:
        from xprof.convert import raw_to_tool_data as rtd
    except ImportError as e:
        print(f"xprof unavailable ({e}); xplane kept at {paths[-1]} — "
              "run the hlo_stats conversion on the rig")
        return
    data, _ = rtd.xspace_to_tool_data([paths[-1]], "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    with open(os.path.join(OUT, "decode8b_hlo_stats.tsv"), "w") as out:
        out.write(data)
    # the tool emits json-ish rows; print the top self-time entries
    import csv
    import io
    rows = list(csv.reader(io.StringIO(data)))
    if not rows:
        print("empty hlo_stats")
        return
    head = rows[0]
    try:
        i_self = head.index("Total self time (us)")
    except ValueError:
        i_self = None
    print("\n=== top fusions by self time ===")
    if i_self is not None:
        body = sorted(rows[1:],
                      key=lambda r2: -float(r2[i_self] or 0))[:25]
        i_cat = head.index("HLO category") if "HLO category" in head else 0
        i_bb = (head.index("Bound by") if "Bound by" in head else None)
        i_name = (head.index("HLO name") if "HLO name" in head else 1)
        for r2 in body:
            bb = r2[i_bb] if i_bb is not None else "?"
            print(f"{float(r2[i_self]):>12.0f} us  {bb:>8}  "
                  f"{r2[i_cat][:20]:>20}  {r2[i_name][:80]}")
    else:
        print(data[:4000])


if __name__ == "__main__":
    main()
