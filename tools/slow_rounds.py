#!/usr/bin/env python3
"""The soak that reads the ``slow_round`` records (docs/OBSERVABILITY.md
"A slow round"; ROADMAP.md S4): runs of a benchmark cell, one process a
run, and per run what the benchmark's last line says beside what the
engine said of its own rounds.

    python tools/slow_rounds.py cost
    python tools/slow_rounds.py run  --workload serve-decode --seed 7 --seconds 45 [--trace 1] [--ring 1]
    python tools/slow_rounds.py soak --workload serve-decode --seeds 5400000001-12 \
        [--variants change,parent,ring] [--parent .scratch/parent] [--trace 0]

``run`` is ``benchmarks/run.py`` in this process (unchanged: nothing
under ``benchmarks/`` knows of this file) with three taps on the
program: every ``slow_round`` the flight recorder takes is kept, the
engine's counters are read as the gateway drains it, and the driver's
window is kept to say which records fell inside it.  A traced run also
lists the device's idle gaps over 100 ms in the traced window with the
program's spans over them and the record of the same round.  ``--ring
1`` turns the span ring on (``InferenceConfig.trace=True``).  What it
found goes to ``chiprun_out/slow_rounds/<cell>.s<seed>.t<trace>[.ring].json``.

``soak`` runs ``run`` for each seed and variant in turn (``parent``: the
plain benchmark in the tree ``--parent`` names; the variants of one seed
are one comparison and share it), never touches JAX itself, and appends
one line a run to ``chiprun_out/slow_rounds/soak.jsonl``.

``cost`` times the reads and the bookkeeping a round adds, on this
host, without JAX.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "slow_rounds")
GAP_S = 0.1


# --------------------------------------------------------------------------
# cost: what a round adds, host only
# --------------------------------------------------------------------------

def cost(n: int = 200_000) -> dict:
    import timeit
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference.failures import (USAGE_PERIOD_S,
                                                  FailureConfig,
                                                  FailurePolicy)
    from deepspeed_tpu.telemetry.host import thread_usage
    tm = {k: 1.0 for k in ("steps", "schedule_ms", "stage_ms", "device_ms",
                           "wait_ms", "readback_ms")}
    rw = FailurePolicy(FailureConfig(), tm).rounds
    stamps = {"queued_us": 20.0, "fn_us": 5000.0, "taken_us": 20.0,
              "hop_us": 40.0}

    def one_round():
        t = time.perf_counter()
        rw.cut_dispatch(t, t, t, t, False, stamps)
        rw.cut_collect(1, t, t, t, False, stamps)
        rw.end(True)

    def us(fn):
        return round(min(timeit.repeat(fn, number=n, repeat=5)) / n * 1e6, 3)

    out = {"perf_counter_us": us(time.perf_counter),
           "process_time_us": us(time.process_time),
           "getrusage_thread_us": us(thread_usage)}
    # the engine's thread, every round: two cuts written, the round
    # closed (the host's state read once every USAGE_PERIOD_S of them)
    out["round_us"] = us(one_round)
    # ten times a second: the engine thread's getrusage and process_time,
    # the worker's getrusage
    out["host_reads_us_per_s"] = round(
        (2 * out["getrusage_thread_us"] + out["process_time_us"])
        / USAGE_PERIOD_S, 1)
    out["added_us_per_6.5ms_round"] = round(
        out["round_us"] + out["host_reads_us_per_s"] * 6.5e-3, 3)
    out["share_of_6.5ms_round_pct"] = round(
        100 * out["added_us_per_6.5ms_round"] / 6500.0, 4)
    return out


# --------------------------------------------------------------------------
# run: one cell in this process, the program tapped
# --------------------------------------------------------------------------

def idle_gaps(trace_dir, window, records):
    """Device 0's idle gaps over ``GAP_S`` in the traced window, each
    with the engine's spans over it and the record of its round."""
    from benchmarks.lib import program_spans, trace
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    threads, ops, _ = program_spans.read(path)
    if not ops:
        return None
    if window:
        ops = trace.clip(ops, window)
    merged = trace._union([(s, e) for s, e, _ in ops])
    lo, hi = window or (merged[0][0], merged[-1][1])
    spans = [sp for evs in threads.values() for sp in evs]
    by_sid = {r.get("sid"): r for r in records}
    edges = [(lo, lo)] + merged + [(hi, hi)]
    gaps = []
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 - e0 < GAP_S:
            continue
        over = [(s, e, nm, st) for s, e, nm, st in spans
                if s < s1 and e > e0 and nm.startswith("ds.")]
        sids = sorted({int(st["sid"]) for _, _, _, st in over if "sid" in st})
        # a round holds the launch of step N+1 and the wait of step N
        rec = next((by_sid[k] for s in sids for k in (s, s - 1, s + 1)
                    if k in by_sid), None)
        gaps.append({
            "at_s": round(e0 - lo, 4), "gap_s": round(s1 - e0, 4),
            "spans": sorted({f"{nm}[{round(min(e, s1) - max(s, e0), 3)}s]"
                             + (f" slow={st['slow']}" if "slow" in st else "")
                             for s, e, nm, st in over}),
            "sids": sids,
            "record": rec and {k: rec.get(k) for k in
                               ("sid", "where", "by", "round_ms", "mean_ms",
                                "wait_ms", "fn_ms", "gc_ms", "next_ready")}})
    marked = [(nm, st.get("slow"), round(e - s, 4)) for s, e, nm, st in spans
              if "slow" in st and lo <= s < hi]
    gcs = [(e - s) for s, e, nm, _ in spans
           if nm == "ds.host.gc" and lo <= s < hi]
    waits = [st for _, _, nm, st in spans if nm == "ds.serve.wait"
             and "fn_us" in st]
    return {"window_s": round(hi - lo, 4), "gaps_over_100ms": gaps,
            "phases_marked_slow": marked,
            "gc_spans": len(gcs), "gc_spans_s": round(sum(gcs), 5),
            "gc_longest_s": round(max(gcs), 5) if gcs else 0.0,
            "waits_with_stamps": len(waits)}


def run(args) -> int:
    sys.path.insert(0, ROOT)
    os.makedirs(OUT, exist_ok=True)
    tag = f"{args.workload}.s{args.seed}.t{args.trace}" \
        + (".ring" if args.ring else "")
    found = {"workload": args.workload, "seed": args.seed,
             "trace": args.trace, "ring": args.ring, "records": []}

    from deepspeed_tpu.telemetry import flight
    from deepspeed_tpu.inference import engine as E
    note0 = flight.FlightRecorder.note

    def note(self, kind, **info):
        if kind == "slow_round":
            found["records"].append(dict(info))
        return note0(self, kind, **info)
    flight.FlightRecorder.note = note

    init0, drain0 = E.InferenceEngine.__init__, E.InferenceEngine.drain

    def init(self, model, config=None, *a, **k):
        if args.ring and config is not None:
            import dataclasses
            config = dataclasses.replace(config, trace=True)
        init0(self, model, config, *a, **k)

    def drain(self, *a, **k):
        snap = self.metrics_snapshot()
        rw = self._round
        found["engine"] = {
            "slow_rounds": snap.get("serving_slow_rounds_total"),
            "slow_round_seconds": snap.get(
                "serving_slow_round_seconds_total"),
            "loop_lag_ms": snap.get("serving_gateway_event_loop_lag_ms"),
            "rounds": rw.n, "mean_round_ms": round(rw.mean_ms, 3),
            "thread_cpu_rate": round(rw.thread_rate, 3),
            "other_cpu_rate": round(rw.other_rate, 3),
            "gc_count": rw.gc.count, "gc_total_s": round(rw.gc.total_s, 4),
            "gc_long": [(round(d * 1e3, 1), g, th)
                        for _, d, g, th in rw.gc.long],
            "ring_spans": len(self.tracer), "steps": int(self.timings["steps"])}
        return drain0(self, *a, **k)
    E.InferenceEngine.__init__, E.InferenceEngine.drain = init, drain

    from benchmarks.lib.drivers import serve
    run0 = serve.run

    def tapped(ctx):
        rec = run0(ctx)
        found["window"] = rec.get("window")
        found["trace_dir"] = rec.get("trace_dir")
        return rec
    serve.run = tapped

    import benchmarks.run as R
    from benchmarks.lib import trace as tracelib
    reduce0 = tracelib.reduce_dir

    def reduce_dir(*a, **k):
        red = reduce0(*a, **k)
        found["trace_window"] = red and red.get("window")
        return red
    tracelib.reduce_dir = reduce_dir

    sys.argv = ["benchmarks/run.py", "--workload", args.workload, "--seed",
                str(args.seed), "--seconds", str(args.seconds), "--trace",
                str(args.trace)] + (["--rehearse"] if args.rehearse else [])
    rc = R.main()
    w = found.get("window") or {}
    for r in found["records"]:
        r["in_window"] = bool(w) and w["t_open"] <= r["t_s"] < w["t_close"]
    if args.trace and found.get("trace_dir"):
        found["trace"] = idle_gaps(found["trace_dir"],
                                   found.get("trace_window"),
                                   found["records"])
    found["rc"] = rc
    with open(os.path.join(OUT, tag + ".json"), "w") as f:
        json.dump(found, f, default=float)
    # what comes back from the chip's machine is capped: the profile and
    # the requests' records have been read, only what was found is kept
    from benchmarks.lib import common
    shutil.rmtree(os.path.join(common.OUT_DIR, "trace." + tag.replace(
        ".ring", "")), ignore_errors=True)
    for name in os.listdir(common.OUT_DIR):
        if name.startswith(f"requests.{tag.replace('.ring', '')}."):
            os.remove(os.path.join(common.OUT_DIR, name))
    return rc


# --------------------------------------------------------------------------
# soak: one process a run, one line a run
# --------------------------------------------------------------------------

def seeds_of(text: str):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        a = int(a)
        # 5400000001-12: the last digits replaced
        b = int(str(a)[:len(str(a)) - len(b)] + b) if b else a
        out.extend(range(a, b + 1))
    return out


def last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return obj
    return None


def soak(args) -> int:
    os.makedirs(OUT, exist_ok=True)
    variants = args.variants.split(",")
    worst = 0
    for i, seed in enumerate(seeds_of(args.seeds)):
        # parent, change, change, parent: the order turns with the seed
        order = variants[i % len(variants):] + variants[:i % len(variants)]
        for v in order:
            tail = ["--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace",
                    str(args.trace)] + (["--rehearse"] if args.rehearse
                                        else [])
            if v == "parent":
                cwd = os.path.join(ROOT, args.parent)
                cmd = [sys.executable, "benchmarks/run.py"] + tail
            else:
                cwd = ROOT
                cmd = [sys.executable, "tools/slow_rounds.py", "run"] + tail \
                    + (["--ring", "1"] if v == "ring" else [])
            t0 = time.time()
            p = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True)
            res = last_json(p.stdout) or {}
            line = {"workload": args.workload, "seed": seed, "variant": v,
                    "rc": p.returncode, "wall_s": round(time.time() - t0, 1),
                    "correct": res.get("correct"),
                    "failed": res.get("failed"),
                    "metrics": {k: m["value"] for k, m in
                                (res.get("metrics") or {}).items()},
                    "log_lines": sum(1 for ln in p.stderr.splitlines()
                                     if "slow_round:" in ln)}
            tag = f"{args.workload}.s{seed}.t{args.trace}" \
                + (".ring" if v == "ring" else "")
            path = os.path.join(OUT, tag + ".json")
            if v != "parent" and os.path.exists(path):
                with open(path) as f:
                    found = json.load(f)
                line["engine"] = found.get("engine")
                line["records"] = found["records"]
                line["trace"] = found.get("trace")
            if p.returncode != 0:
                worst = p.returncode
                line["stderr_tail"] = p.stderr[-2000:]
            with open(os.path.join(OUT, "soak.jsonl"), "a") as f:
                f.write(json.dumps(line, default=float) + "\n")
            recs = line.get("records") or []
            print(json.dumps({  # tpulint: disable=print — the CLI's one line a run
                "workload": args.workload, "seed": seed, "variant": v,
                "rc": p.returncode, "wall_s": line["wall_s"],
                "correct": line["correct"], **line["metrics"],
                "slow_rounds": [(r["where"] + (":" + r["by"] if r.get("by")
                                               else ""),
                                 round(r["round_ms"] - r["mean_ms"], 1),
                                 r["in_window"]) for r in recs],
                # the long ones whole, the traced run's gaps
                "long": [r for r in recs if r["round_ms"] > 500.0],
                "trace": line.get("trace")}, default=float),
                flush=True)
    return worst


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="mode", required=True)
    sub.add_parser("cost")
    for name in ("run", "soak"):
        q = sub.add_parser(name)
        q.add_argument("--workload", required=True)
        q.add_argument("--seconds", type=float, default=45.0)
        q.add_argument("--trace", type=int, choices=(0, 1), default=0)
        q.add_argument("--rehearse", action="store_true")
        if name == "run":
            q.add_argument("--seed", type=int, default=0)
            q.add_argument("--ring", type=int, choices=(0, 1), default=0)
        else:
            q.add_argument("--seeds", required=True)
            q.add_argument("--variants", default="change")
            q.add_argument("--parent", default=".scratch/parent")
    args = p.parse_args()
    if args.mode == "cost":
        print(json.dumps(cost()))  # tpulint: disable=print — the CLI's output
        return 0
    return run(args) if args.mode == "run" else soak(args)


if __name__ == "__main__":
    sys.exit(main())
