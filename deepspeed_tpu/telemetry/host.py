"""The host's state beside the serving loop's stamps
(docs/OBSERVABILITY.md "A slow round").

The loop's own clock says THAT a round ran long and in which phase; what
the host was doing meanwhile says WHY.  Three sources, all JAX-free:

* :func:`thread_usage` — one ``getrusage(RUSAGE_THREAD)``: the calling
  thread's CPU seconds and its voluntary / involuntary context switches.
  A system call: the serving loop reads it ten times a second on the
  engine's thread and on the watchdog's worker, and when a round or a
  guarded call was long.
* :func:`watch_gc` — ONE ``gc.callbacks`` entry a process, however many
  engines are built.  Every collection is timed on the thread that ran
  it: its seconds go to a running total (read by attribute, no call),
  those of a millisecond or more to a short ring with their generation
  and thread, and each one is a ``ds.host.gc`` span — a ``TraceMe`` on
  the profiler's clock, and a record in the ring of every tracer handed
  to :func:`watch_gc` whose ring is on.
* :class:`HostSampler` — what costs a file read and is therefore read
  only when a round WAS slow: load average, the CPU pressure total, the
  cgroup's throttling (v1 or v2) and the run delay of named threads,
  each with its rise since the previous reading.
  A file the host does not have is left out.
"""

from __future__ import annotations

import gc
import os
import resource
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

from .tracer import _traceme

_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
_getrusage = resource.getrusage

GC_SPAN = "ds.host.gc"
# a collection this long is kept with its generation and thread
GC_LONG_S = 1e-3


def thread_usage() -> Tuple[float, int, int]:
    """(CPU seconds, voluntary switches, involuntary switches) of the
    calling thread, from one ``getrusage``."""
    ru = _getrusage(_RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_nvcsw, ru.ru_nivcsw


def _on_event_loop() -> bool:
    """Is the calling thread running an asyncio event loop?  Never
    imports asyncio itself: a process that has not has no such thread."""
    aio = sys.modules.get("asyncio")
    return aio is not None and aio._get_running_loop() is not None


class GcWatch:
    """The process's collections, timed.  One collection runs at a time
    (the collector is not reentrant), so one slot holds the open one."""

    def __init__(self):
        self.total_s = 0.0          # seconds of every collection ended
        self.count = 0
        # (end on perf_counter, seconds, generation, thread name)
        self.long: Deque[Tuple[float, float, int, str]] = deque(maxlen=64)
        self._t0: Optional[int] = None
        self._tm: Any = None
        self._rings: List[weakref.ref] = []

    def add_ring(self, tracer) -> None:
        if tracer is not None and not any(r() is tracer
                                          for r in self._rings):
            self._rings = [r for r in self._rings if r() is not None]
            self._rings.append(weakref.ref(tracer))

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
            # the span lies on the thread that collects.  Not on an
            # event loop's thread: benchmarks/lib/program_spans.py tells
            # the loop's thread from the engine's by its holding
            # ``ds.gateway.route`` spans ONLY, and books idle time
            # otherwise (ROADMAP.md W0 (r)); its seconds are counted
            tm = None if _on_event_loop() else _traceme()
            if tm is not None:
                tm = tm(GC_SPAN, gen=info["generation"])
                tm.__enter__()
            self._tm = tm
            return
        t1 = time.perf_counter_ns()
        tm, self._tm = self._tm, None
        if tm is not None:
            tm.__exit__(None, None, None)
        t0, self._t0 = self._t0, None
        if t0 is None:              # registered while one was running
            return
        dur = (t1 - t0) * 1e-9
        self.total_s += dur
        self.count += 1
        name = None
        for ref in self._rings:
            tr = ref()
            if tr is not None and tr.enabled:
                name = name or threading.current_thread().name
                tr._push(GC_SPAN, "gc", t0, t1 - t0, 0,
                         {"gen": info["generation"], "thread": name})
        if dur >= GC_LONG_S:
            self.long.append((t1 * 1e-9, dur, info["generation"],
                              name or threading.current_thread().name))

    def ended_in(self, lo: float, hi: float) -> List[Dict[str, Any]]:
        """The long collections that ended inside ``[lo, hi]`` on
        ``perf_counter``: ``{"gen", "ms", "thread"}`` each."""
        return [{"gen": g, "ms": round(d * 1e3, 3), "thread": th}
                for end, d, g, th in list(self.long) if lo <= end <= hi]


_GC: Optional[GcWatch] = None
_GC_LOCK = threading.Lock()


def watch_gc(tracer=None) -> GcWatch:
    """The process's :class:`GcWatch`, registered in ``gc.callbacks`` by
    the first call and never again; ``tracer``'s ring takes the
    ``ds.host.gc`` records from now on (held weakly)."""
    global _GC
    with _GC_LOCK:
        if _GC is None:
            _GC = GcWatch()
            gc.callbacks.append(_GC)
        _GC.add_ring(tracer)
    return _GC


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


_CPU_STAT_PATHS = ("/sys/fs/cgroup/cpu.stat",              # v2
                   "/sys/fs/cgroup/cpu/cpu.stat",          # v1
                   "/sys/fs/cgroup/cpu,cpuacct/cpu.stat")


class HostSampler:
    """Readings that cost a file each, taken only when asked (a slow
    round, and once at ``reset_metrics()`` for the baseline).  Every
    cumulative one comes with its rise since the previous reading, and
    ``since_s`` says how long ago that was."""

    def __init__(self):
        self._cpu_stat = next((p for p in _CPU_STAT_PATHS
                               if os.path.exists(p)), None)
        self._prev: Dict[str, float] = {}
        self._t_prev: Optional[float] = None

    def _cumulative(self, tids: Iterable[int]) -> Dict[str, float]:
        out: Dict[str, float] = {}
        text = _read("/proc/pressure/cpu")
        if text:
            for line in text.splitlines():
                if line.startswith("some") and "total=" in line:
                    out["pressure_ms"] = \
                        int(line.rsplit("total=", 1)[1]) / 1e3
        text = self._cpu_stat and _read(self._cpu_stat)
        if text:
            for line in text.splitlines():
                key, _, val = line.partition(" ")
                if key == "nr_throttled":
                    out["nr_throttled"] = float(val)
                elif key == "throttled_usec":           # v2
                    out["throttled_ms"] = int(val) / 1e3
                elif key == "throttled_time":           # v1, ns
                    out["throttled_ms"] = int(val) / 1e6
        delay = None
        for tid in tids:
            text = tid and _read(f"/proc/self/task/{tid}/schedstat")
            if text:
                delay = (delay or 0.0) + int(text.split()[1]) / 1e6
        if delay is not None:
            out["run_delay_ms"] = delay
        return out

    def read(self, tids: Iterable[int] = ()) -> Dict[str, float]:
        """``load1`` and, for each cumulative reading the host has,
        ``<name>_rise``: its rise since the previous call (absent on the
        first).  ``tids``: native thread ids whose run delay (runnable,
        not running) is summed into ``run_delay_ms``."""
        now = time.perf_counter()
        cur = self._cumulative(tids)
        out: Dict[str, float] = {}
        try:
            out["load1"] = os.getloadavg()[0]
        except OSError:
            pass
        prev, self._prev = self._prev, cur
        t_prev, self._t_prev = self._t_prev, now
        if t_prev is not None:
            out["since_s"] = round(now - t_prev, 3)
            for k, v in cur.items():
                if k in prev:
                    out[k + "_rise"] = round(v - prev[k], 3)
        return out
