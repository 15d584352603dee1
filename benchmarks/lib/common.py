"""What every driver shares: the files of a cell, the device, the compile
cache, the lines a run prints."""

import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
# fixed, git-ignored, inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".jax_compile_cache")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")


def note(_note: str, **kw):
    """An earlier line: what the driver does not read."""
    sys.stdout.write(json.dumps({"note": _note, **kw}, default=float) + "\n")
    sys.stdout.flush()


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (configuration and metric names hold dashes
    and dots, which ``import`` does not take)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    traffic["_file"] = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def metrics_of(bench: dict, cell: dict, group: str):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader_path(folder: str, name: str) -> str:
    """The reader of a metric: ``<folder>/<name>.py``.  A quantity split
    by the cells that report it, because they report different end-to-end
    metrics for it to move, is named ``<cells>.<reader>`` in each half and
    read by the one ``<folder>/<reader>.py``."""
    own = os.path.join(BENCH_DIR, folder, name + ".py")
    if os.path.exists(own) or "." not in name:
        return own
    return os.path.join(BENCH_DIR, folder, name.rsplit(".", 1)[1] + ".py")


def apply_rehearsal(config: dict, traffic: dict):
    """``--rehearse``: the same control flow at the tiny sizes the files
    give under ``rehearse``."""
    for d in (config, traffic):
        for k, v in d.pop("rehearse", {}).items():
            if isinstance(v, dict) and isinstance(d.get(k), dict):
                d[k] = {**d[k], **v}
            else:
                d[k] = v


def enable_compile_cache():
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says if it
    is set, else the one fixed directory in the checkout."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def cache_size(path):
    n = b = 0
    for root, _, files in os.walk(path or ""):
        for f in files:
            n += 1
            b += os.path.getsize(os.path.join(root, f))
    return {"dir": path, "entries": n, "bytes": b}


class CompileWatch:
    """Counts XLA compiles and persistent-cache hits of this process
    through jax.monitoring (the idiom of chip_smoke._watch_compiles)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def require_devices(chips: int, rehearse: bool):
    """The cell's devices, or exit non-zero with no result: no CPU
    fallback for a measurement."""
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: platform is {devs[0].platform!r}, not "
                         "'tpu'; no result (use --rehearse for a CPU dry run)")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; no result")
    return devs[:chips]


def device_block(devs) -> dict:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:      # a backend without memory statistics
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def last_line_faults(result: dict, traced: bool, on_chip: bool,
                     reduced: dict = None) -> list:
    """What the driver would refuse in a run's last line, found here so
    that a builder meets it at their own chip run: the five keys, and in
    a traced run on the chip ``0 < busy_s <= window_s``, both cut by the
    one ``bench.trace.window`` span (``trace.reduce``).  A run off the chip
    (the rehearsal) carries neither number.  Each fault says which of the
    two is wrong, with the stamps it was cut from."""
    faults = [f"key {k!r} is missing" for k in
              ("correct", "attempted", "failed", "metrics", "device")
              if k not in result]
    device = result.get("device", {})
    if not (traced and on_chip):
        faults += [f"device.{k} stands in a run that is not a traced run on "
                   "the chip" for k in ("busy_s", "window_s") if k in device]
        return faults
    busy, window = device.get("busy_s"), device.get("window_s")
    red = reduced or {}
    where = (f"window cut by {red.get('cut_by')} at {red.get('window')}, first "
             f"operation starts at {red.get('first_op_s')}, last one ends at "
             f"{red.get('last_op_s')} (seconds on the trace's clock), "
             f"{red.get('op_events')} operations inside")

    def positive(x):          # a NaN is not above 0 either
        return isinstance(x, (int, float)) and x > 0
    if not positive(window):
        faults.append(f"device.window_s is {window!r}, not a number above 0: "
                      + (where if reduced else "the trace gave nothing"))
    elif not positive(busy):
        faults.append(f"device.busy_s is {busy!r}, not a number above 0: no "
                      f"operation ran on the device inside the window; {where}")
    elif busy > window:
        faults.append(f"device.busy_s {busy!r} is over device.window_s "
                      f"{window!r}: operations were not clipped to the "
                      f"window; {where}")
    return faults


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of a non-empty list."""
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window_token_gaps_ms(rec) -> list:
    """Gaps between consecutive tokens of one stream on the wire, for ALL
    gaps whose later token arrived inside the window.  Client's clock."""
    w = rec["window"]
    return [(b - a) * 1e3 for r in rec["requests"]
            for a, b in zip(r["token_t"], r["token_t"][1:])
            if w["t_open"] <= b < w["t_close"]]


class Setup:
    """The parts of set-up, for the earlier line that breaks it down."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.parts = {}
        self._last = time.perf_counter()
        self.parts["imports"] = self._last - t_process_start

    def mark(self, name: str):
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return time.perf_counter() - self.t0


def start_trace(trace_dir: str):
    """Start the profiler with the Python tracer off: it stamps every
    Python call, which slows a host-bound loop by a large factor and
    would be read as device idle.  ``TraceAnnotation`` spans stay.

    Returns the traced window's own span (``bench.trace.window``), opened
    on the line after the profiler has started: its two stamps lie on the
    clock of the device's ``XLA Ops`` lines, and ``trace.reduce`` clips
    every operation to them.  Hand it to ``stop_trace``."""
    import jax
    from benchmarks.lib.trace import WINDOW_SPAN
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    return span


def stop_trace(span):
    """Close the window's span, then stop the profiler: what the device
    tracer records while ``stop_trace`` stalls is outside the window."""
    import jax
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()
