"""Device & compiler observability (docs/OBSERVABILITY.md "Device &
compiler telemetry").

PR 5's telemetry sees *when* the host waits; this module sees *what the
device and compiler are doing*: per-program ``compiled.cost_analysis()``
(flops / bytes accessed / HLO size), derived achieved-utilization gauges
(``serving_mfu`` / ``serving_hbm_bw_util`` — computed at *read* time
from the existing step-timing counters, never on the hot path), and
``device.memory_stats()`` polled at phase boundaries (the probe pattern
of ``runtime/runtime_utils.py:see_memory_usage`` — one host call, no
device sync).  These are exactly the profiling-derived signals
DeepCompile (arxiv 2504.09983) argues an autotuner must consume, and
the live complement of the bench's one-shot MFU number.

Design constraints, same priority order as the rest of telemetry/:

* **Zero cost when off.**  An engine with device telemetry disabled
  constructs NO :class:`DeviceTelemetry` — no ``cost_analysis`` calls,
  no memory polls, no clock reads added anywhere
  (tests/test_device_telemetry.py holds the bar).
* **Loud-but-graceful degradation.**  Every probe is best-effort per
  backend: CPU has ``cost_analysis`` but no ``memory_stats`` (returns
  None) and no published peak — missing inputs make the derived gauges
  ABSENT from the exposition (FnGauge's ``None`` contract), never zero
  and never a crash.  One warning per engine per missing capability.
* **Probe at boundaries, read at export.**  ``cost_analysis`` runs once
  per compiled program (an explicit AOT lower+compile of an
  already-warm program — host/compiler work only); memory polls run at
  engine phase boundaries (health checks, dumps, bench captures) —
  never inside a serving-loop-marked method.

The compile/retrace *counters* deliberately do NOT live here: they are
plain host counter bumps on the engines' existing executable-cache fill
paths, cheap enough to stay always-on like the rest of the registry.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from ..utils.logging import logger
from .metrics import MetricsRegistry

# bf16 peak FLOP/s and HBM bandwidth (bytes/s) per chip generation —
# the one table of this package: the live gauges read it here.  Matched
# by substring against device_kind (lowercased); unknown kinds (the CPU
# included) yield None -> absent gauges.
PEAK_FLOPS = {"v4": 275e12, "v5 lite": 197e12, "v5e": 197e12,
              "v5p": 459e12, "v5": 459e12, "v6e": 918e12, "v6": 918e12}
PEAK_HBM_BW = {"v4": 1.2e12, "v5 lite": 0.82e12, "v5e": 0.82e12,
               "v5p": 2.77e12, "v5": 2.77e12, "v6e": 1.64e12,
               "v6": 1.64e12}


def _match_peak(table: Dict[str, float], kind: str) -> Optional[float]:
    kind = (kind or "").lower()
    for k, v in table.items():
        if k in kind:
            return v
    return None


def peak_flops(device=None) -> Optional[float]:
    """Published bf16 peak FLOP/s for ``device`` (default: the default
    backend's first device); None when unknown — CPU and virtualized
    kinds have no honest peak, and a made-up one would make the MFU
    gauge a lie."""
    d = device if device is not None else _default_device()
    if d is None:
        return None
    return _match_peak(PEAK_FLOPS, getattr(d, "device_kind", ""))


def peak_hbm_bw(device=None) -> Optional[float]:
    """Published HBM bandwidth (bytes/s); None when unknown."""
    d = device if device is not None else _default_device()
    if d is None:
        return None
    return _match_peak(PEAK_HBM_BW, getattr(d, "device_kind", ""))


def _default_device():
    try:
        import jax
        return jax.devices()[0]
    except Exception as e:
        logger.warning("device telemetry: no default device (%s)",
                       type(e).__name__)
        return None


def cost_analysis_of(compiled) -> Dict[str, float]:
    """Robust extraction from a ``jax.stages.Compiled``: whatever of
    ``flops`` / ``bytes_accessed`` / ``peak_bytes`` / ``hlo_bytes`` the
    backend reports — missing fields are ABSENT from the dict, never
    zero-filled (an absent field keeps its derived gauge absent)."""
    out: Dict[str, float] = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if "flops" in cost:
            out["flops"] = float(cost["flops"])
        if "bytes accessed" in cost:
            out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception as e:
        logger.warning("cost_analysis unavailable on this backend: %r", e)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["peak_bytes"] = float(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0))
    except Exception as e:
        logger.debug("memory_analysis unavailable: %r", e)
    try:
        out["hlo_bytes"] = float(len(compiled.as_text()))
    except Exception as e:
        logger.debug("compiled.as_text unavailable: %r", e)
    return out


def poll_memory_stats() -> Dict[str, Dict[str, int]]:
    """``device.memory_stats()`` for every local device, keyed by device
    id — the ``see_memory_usage`` probe shape (one host call per device,
    never a device sync).  Devices that report None (CPU) are simply
    absent from the result."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        try:
            s = d.memory_stats()
        except Exception as e:
            logger.debug("memory_stats unavailable on %s: %r", d, e)
            s = None
        if s:
            out[str(d.id)] = {
                "bytes_in_use": int(s.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(s.get("bytes_limit", 0)),
            }
    return out


class DeviceTelemetry:
    """The gated half of device observability for ONE engine: program
    cost table, per-step flop/byte accumulation, derived utilization
    gauges, and memory-stat polling.  Constructed ONLY when device
    telemetry is enabled — a disabled engine holds ``None`` and pays
    nothing.

    ``prefix``: ``"serving"`` or ``"training"`` — the metric-name
    family (tpulint's ``metric-name`` rule).  ``step_ms_fn``: zero-arg
    callable returning the cumulative device-busy milliseconds the
    utilization gauges divide by (the engines pass their existing
    ``device_ms + wait_ms`` counters — read at export time, so the hot
    path takes no new clock reads).  ``peak_flops``/``peak_hbm_bw``:
    explicit overrides (tests; rigs whose kind string lies), default
    resolved from the default device — None leaves the corresponding
    gauge absent."""

    def __init__(self, registry: MetricsRegistry, prefix: str,
                 step_ms_fn, peak_flops: Optional[float] = None,
                 peak_hbm_bw: Optional[float] = None,
                 device=None):
        self.registry = registry
        self.prefix = prefix
        self._step_ms_fn = step_ms_fn
        dev = device if device is not None else _default_device()
        kind = getattr(dev, "device_kind", "")
        self.peak_flops = peak_flops if peak_flops is not None \
            else _match_peak(PEAK_FLOPS, kind)
        self.peak_hbm_bw = peak_hbm_bw if peak_hbm_bw is not None \
            else _match_peak(PEAK_HBM_BW, kind)
        if self.peak_flops is None:
            logger.warning(
                "device telemetry: no published peak for device kind "
                "%r — %s_mfu/%s_hbm_bw_util gauges stay absent",
                getattr(dev, "device_kind", "?"), prefix, prefix)
        # program-key -> cost dict (flops/bytes_accessed/peak_bytes/...)
        self.program_costs: Dict[Any, Dict[str, float]] = {}
        # dispatched work attributed from the cost table (counters so
        # snapshots/JSONL see them; bumped once per dispatch — a dict
        # lookup + two adds, only when telemetry is ON)
        self._c_flops = registry.counter(
            f"{prefix}_model_flops_total",
            "model FLOPs dispatched, attributed from per-program "
            "cost_analysis")
        self._c_bytes = registry.counter(
            f"{prefix}_hbm_bytes_total",
            "HBM bytes accessed by dispatched programs, attributed "
            "from per-program cost_analysis")
        registry.gauge_fn(
            f"{prefix}_mfu", self._mfu,
            "achieved model-FLOPs utilization over the measured steps "
            "(cost-analysis flops / device-busy time / published peak; "
            "absent when the backend reports no flops or has no "
            "published peak)")
        registry.gauge_fn(
            f"{prefix}_hbm_bw_util", self._bw_util,
            "achieved HBM bandwidth utilization (cost-analysis bytes "
            "accessed / device-busy time / published peak bandwidth; "
            "absent when unavailable)")
        # memory gauges are registered lazily on the first poll that
        # actually returns data, so a backend without memory_stats
        # (CPU) exports NO fake zero series
        self._mem_registered = False
        self._warned_mem = False

    # ---- compile observatory ------------------------------------------
    def probe_program(self, key, jitted, args) -> Dict[str, float]:
        """Record one compiled program's cost analysis (memoized by
        ``key``).  Runs an explicit AOT ``lower(*args).compile()`` on
        the already-warm jit function — the ONE deliberately-paid
        duplicate compile per program, bought only when device
        telemetry is on, outside any timed/hot region (see the
        cost-analysis caveats in docs/OBSERVABILITY.md)."""
        cached = self.program_costs.get(key)
        if cached is not None:
            return cached
        import time
        cost: Dict[str, float] = {}
        try:
            t0 = time.perf_counter()
            compiled = jitted.lower(*args).compile()
            cost = cost_analysis_of(compiled)
            cost["compile_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
        except Exception as e:
            logger.warning("device telemetry: cost probe failed for "
                           "%r (%s: %s)", key, type(e).__name__,
                           str(e).splitlines()[0][:120] if str(e) else "")
        self.program_costs[key] = cost
        return cost

    def on_dispatch(self, key) -> None:
        """Attribute one dispatched execution of program ``key`` to the
        flop/byte counters."""
        cost = self.program_costs.get(key)
        if not cost:
            return
        f = cost.get("flops")
        b = cost.get("bytes_accessed")
        if f:
            self._c_flops.inc(f)
        if b:
            self._c_bytes.inc(b)

    # ---- derived utilization gauges (read-time, FnGauge) --------------
    def _busy_s(self) -> Optional[float]:
        try:
            ms = float(self._step_ms_fn())
        except Exception:  # tpulint: disable=silent-except — a dead engine's counters read as no sample
            return None
        return ms / 1e3 if ms > 0 else None

    def _mfu(self) -> Optional[float]:
        busy = self._busy_s()
        flops = self._c_flops.value()
        if busy is None or not flops or not self.peak_flops:
            return None
        return flops / busy / self.peak_flops

    def _bw_util(self) -> Optional[float]:
        busy = self._busy_s()
        nbytes = self._c_bytes.value()
        if busy is None or not nbytes or not self.peak_hbm_bw:
            return None
        return nbytes / busy / self.peak_hbm_bw

    # ---- memory accounting --------------------------------------------
    def poll_memory(self) -> Dict[str, Dict[str, int]]:
        """Poll ``memory_stats`` for every local device and publish the
        per-device gauges (labeled by device id).  Called at phase
        boundaries only — engine health checks, drains, dumps, bench
        captures — never per step.  On backends without memory stats
        this warns ONCE and the gauges stay absent."""
        stats = poll_memory_stats()
        if not stats:
            if not self._warned_mem:
                self._warned_mem = True
                logger.warning(
                    "device telemetry: memory_stats unavailable on "
                    "this backend — %s_hbm_* gauges stay absent",
                    self.prefix)
            return stats
        if not self._mem_registered:
            self._mem_registered = True
            p = self.prefix
            self._g_in_use = self.registry.gauge(
                f"{p}_hbm_bytes_in_use", "device bytes in use at the "
                "last phase-boundary poll")
            self._g_peak = self.registry.gauge(
                f"{p}_hbm_peak_bytes_in_use",
                "peak device bytes in use")
            self._g_limit = self.registry.gauge(
                f"{p}_hbm_bytes_limit", "device memory capacity")
        for did, s in stats.items():
            self._g_in_use.set(s["bytes_in_use"], device=did)
            self._g_peak.set(s["peak_bytes_in_use"], device=did)
            self._g_limit.set(s["bytes_limit"], device=did)
        return stats

    # ---- export --------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """JSON-able device-telemetry summary (what a benchmark embeds):
        per-program costs, the derived utilizations (None when absent),
        and the last memory poll."""
        mfu = self._mfu()
        bw = self._bw_util()
        return {
            "programs": {self._key_str(k): dict(v)
                         for k, v in self.program_costs.items()},
            "model_flops_total": self._c_flops.value(),
            "hbm_bytes_total": self._c_bytes.value(),
            "mfu": None if mfu is None else round(mfu, 6),
            "hbm_bw_util": None if bw is None else round(bw, 6),
            "peak_flops": self.peak_flops,
            "peak_hbm_bw": self.peak_hbm_bw,
            "memory": self.poll_memory(),
        }

    @staticmethod
    def _key_str(key) -> str:
        return key if isinstance(key, str) else repr(key)
