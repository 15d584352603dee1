#!/usr/bin/env python3
"""One process, one cell, one last line of JSON.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration and traffic are the files BENCHMARK.json names;
the traffic file names its driver; every metric is a reader of its own
(``end_to_end/<name>.py``, ``layer_metrics/<name>.py``).  Nothing here
depends on the name of a cell, a configuration or a metric.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the same control flow
on the CPU at the tiny sizes the files give under ``rehearse``, and its
last line carries no metric.
"""

import time
T_PROCESS_START = time.perf_counter()       # setup_s counts from here

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--sweep-rate", type=float, default=None,
                   help="open-loop mixes: offer this rate instead of the "
                        "file's (for the one sweep that defines a cell)")
    return p.parse_args()


def main() -> int:
    args = parse()
    from benchmarks.lib import common
    bench, cell, config, traffic = common.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{cell['chips']} "
                "--xla_cpu_enable_concurrency_optimized_scheduler=false")
        common.apply_rehearsal(config, traffic)
    devices = common.require_devices(cell["chips"], args.rehearse)
    cache_dir = common.enable_compile_cache()
    setup = common.Setup(T_PROCESS_START)
    os.makedirs(common.OUT_DIR, exist_ok=True)
    tag = f"{cell['name']}.s{args.seed}.t{args.trace}"
    ctx = {"args": args, "bench": bench, "cell": cell, "config": config,
           "traffic": traffic, "setup": setup, "devices": devices,
           "rehearse": args.rehearse, "sweep_rate": args.sweep_rate,
           "trace_dir": os.path.join(common.OUT_DIR, "trace." + tag),
           "records_path": os.path.join(common.OUT_DIR, f"requests.{tag}.jsonl"),
           "cache_size": lambda: common.cache_size(cache_dir)}
    common.note("start", workload=cell["name"], config=cell["config"],
                traffic=cell["traffic"], seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices), cache_dir=cache_dir,
                rehearse=args.rehearse)
    driver = importlib.import_module(
        "benchmarks.lib.drivers." + traffic["driver"])
    rec = driver.run(ctx)
    rec["config"], rec["traffic"], rec["cell"] = config, traffic, cell

    group, folder = (("per_layer", "layer_metrics") if args.trace
                     else ("end_to_end", "end_to_end"))
    if args.trace:
        from benchmarks.lib import trace as tracelib
        rec["trace"] = tracelib.reduce_dir(
            rec.get("trace_dir"), aliases=config.get("trace_groups"))
        common.note("trace", found=rec["trace"] is not None,
                    **({k: rec["trace"][k] for k in
                        ("busy_s", "window_s", "idle_share", "devices",
                         "op_events", "exposed_collective_s", "cut_by",
                         "window", "first_op_s", "last_op_s")}
                       if rec["trace"] else {}))
    if not args.rehearse:
        from benchmarks.lib.peaks import peaks_for
        rec["peaks"] = peaks_for(devices[0].device_kind)
    metrics = {}
    for m in common.metrics_of(bench, cell, group):
        reader = common.load_module(common.reader_path(folder, m["name"]),
                                    "metric_" + m["name"].replace(".", "_"))
        value = reader.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = rec["device"]
    result = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if args.trace and rec.get("trace"):
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    if args.rehearse:
        # a CPU dry run proves control flow; it measures nothing
        result["metrics"] = {}
        result["rehearsal"] = True
        common.note("rehearsal_values", values=metrics)
    # each number compared beside its limit: last in the line, and as the
    # last lines on standard error
    checks = rec.get("compared", {})
    result["compared_with_reference"] = {
        "file": config["reference"]["file"], "checks": checks}
    on_chip = not args.rehearse and device["platform"] == "tpu"
    faults = common.last_line_faults(result, traced=bool(args.trace),
                                     on_chip=on_chip, reduced=rec.get("trace"))
    if faults:
        for f in faults:
            sys.stderr.write("benchmark: refusing its own last line: "
                             + f + "\n")
        return 1
    for name, c in checks.items():
        sys.stderr.write(f"compared {name}: rel={c['rel']!r} limit={c['tol']!r} "
                         f"ok={c['ok']} (system={c['system']!r} "
                         f"reference={c['reference']!r})\n")
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0 if args.rehearse or device["platform"] == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main())
