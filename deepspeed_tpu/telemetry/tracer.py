"""Host-side span tracing for the serving and training loops.

A :class:`SpanTracer` is the one span recorder of the program.  Every
span goes to two sinks:

* **the ring** (gated by ``enabled``) — a preallocated buffer of (name,
  track, begin, duration) records on the monotonic
  ``time.perf_counter_ns`` clock.  It makes the pipelined serving loop's
  overlap structure *visible*: each pipeline stage (schedule / stage /
  dispatch / wait / readback) records onto its own track, so the
  exported Chrome trace shows dispatch-ahead steps overlapping device
  compute exactly as they ran.
* **the profiler mirror** (always armed) — the same span entered as a
  profiler ``TraceMe`` (``jax.profiler.TraceAnnotation``), its args as
  the event's stats.  Without a profiler session that is a check of one
  flag in C++; inside one the span lands on the ``/host:`` plane of the
  session's ``.xplane.pb``, on the clock of the device's ``XLA Ops``
  lines — host and device share a clock by construction.  This is how a
  serving-loop method reaches ``TraceMe`` (tpulint ``profiler-capture``).
  The class is resolved lazily and only once ``jax`` is already in
  ``sys.modules``: ``telemetry/`` stays JAX-free at import, and a
  process that never imports JAX has no mirror.

Span names are ``ds.<layer>.<phase>`` (docs/OBSERVABILITY.md).

Design constraints (docs/OBSERVABILITY.md):

* **Near-zero cost when disabled** — with the ring off a ``span()``
  reads no clock and pushes nothing (it is the ``TraceMe`` alone, or a
  shared no-op where there is no JAX); a :meth:`SpanTracer.phase` cut
  reads the clock exactly once, the reading the caller's own phase
  accounting needs anyway.
* **Bounded memory** — the ring holds ``capacity`` records; older spans
  are overwritten (``dropped`` counts them), so a long-lived serving
  engine can leave tracing on without growing.
* **No device work** — the tracer only ever touches host integers.
  Recording a span must never force a device sync (enforced tree-wide
  by tpulint's ``telemetry-hotpath`` rule: telemetry calls are banned
  inside jit-traced functions).

Two export formats:

* :meth:`export_chrome_trace` — Chrome trace-event JSON (load in
  Perfetto / ``chrome://tracing``), one thread-track per stage.
* :meth:`export_jsonl` — one JSON object per span, for ad-hoc tooling.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_TRACEME: Any = None       # jax.profiler.TraceAnnotation once resolved


def _traceme():
    """``jax.profiler.TraceAnnotation``, or None while JAX is not
    imported (``import jax`` brings ``jax.profiler`` with it) or where
    the build has no such class.  Never imports anything itself."""
    global _TRACEME
    if _TRACEME is None:
        mod = sys.modules.get("jax.profiler")
        if mod is not None:
            _TRACEME = getattr(mod, "TraceAnnotation", False)
    return _TRACEME or None


class _NoopSpan:
    """Shared do-nothing span: ring off and no profiler to mirror to."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _Span:
    """One live span: a ``TraceMe`` for the profiler (``_tm``, None
    where there is no JAX) and, if the ring is on when it ends, one ring
    record.  ``t0`` is ``perf_counter_ns`` at the start, or None when
    nobody read the clock (a ``span()`` entered with the ring off)."""
    __slots__ = ("_tracer", "_name", "_track", "_args", "_t0", "_tm")

    def __init__(self, tracer: "SpanTracer", name: str,
                 track: Optional[str], args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._args = args
        self._t0: Optional[int] = None
        tm = _traceme()
        self._tm = tm(name, **(args or {})) if tm else None

    def _begin(self, t0_ns: Optional[int]) -> None:
        self._tracer._tls_depth(+1)
        self._t0 = t0_ns
        if self._tm is not None:
            self._tm.__enter__()

    def _end(self, t1_ns: Optional[int]) -> None:
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
        tr = self._tracer
        depth = tr._tls_depth(-1)
        if t1_ns is not None and self._t0 is not None:
            tr._push(self._name, self._track or self._name, self._t0,
                     max(0, t1_ns - self._t0), depth, self._args)

    def set_metadata(self, **args) -> None:
        """Args known only once the span is under way (a count, a
        hand-off time): added to the ring record and to the profiler
        event's stats.  Named as ``TraceMe``'s own method, which is
        what ``span()`` hands out while the ring is off."""
        self._args = {**self._args, **args} if self._args else args
        if self._tm is not None:
            self._tm.set_metadata(**args)

    def __enter__(self):
        self._begin(time.perf_counter_ns()
                    if self._tracer.enabled else None)
        return self

    def __exit__(self, *exc):
        self._end(time.perf_counter_ns()
                  if self._tracer.enabled else None)
        return False


class SpanTracer:
    """The program's span recorder: a preallocated ring on
    ``perf_counter_ns`` plus the always-armed profiler mirror.

    Spans are recorded two ways, both live (a profiler ``TraceMe``
    cannot be written after the fact):

    * ``with tracer.span("ds.serve.prefix_match", track="schedule"):``
      — a context manager around its body; nesting is tracked per
      thread (``depth``) so tooling can reconstruct the stack without
      relying on time containment alone.  ``.set_metadata(k=v)`` adds
      args known only inside the body.
    * ``t = tracer.phase("ds.serve.stage", track="stage", sid=7)`` — one
      call at a phase boundary: ends the phase this thread has open (if
      any), begins the named one, and returns the ONE
      ``time.perf_counter()`` reading (float seconds) taken for both.
      The loops use it where they already read the clock for
      ``engine.timings``, so tracing adds no clock reads to the hot
      path.  :meth:`phase_end` closes the last phase of a sequence;
      :meth:`phase_set` adds args to the open one.
    """

    def __init__(self, capacity: int = 1 << 16, enabled: bool = False):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self.enabled = bool(enabled)
        # the ring is allocated lazily on the first recorded span, so a
        # never-enabled tracer (every engine constructs one) costs one
        # None attribute, not a capacity-sized list
        self._buf: Optional[List[Optional[tuple]]] = None
        self._cursor = 0
        self._total = 0            # spans ever recorded (dropped included)
        # per thread (the gateway's event loop and its engine thread
        # share their backend's tracer): live nesting depth, and the
        # open phase
        self._tls = threading.local()

    # ------------------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self._buf = None
        self._cursor = 0
        self._total = 0

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wraparound."""
        return max(0, self._total - self.capacity)

    def __len__(self) -> int:
        return min(self._total, self.capacity)

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    def _tls_depth(self, delta: int = 0) -> int:
        tl = self._tls
        d = max(0, getattr(tl, "depth", 0) + delta)
        tl.depth = d
        return d

    def _push(self, name: str, track: str, ts_ns: int, dur_ns: int,
              depth: int, args: Optional[Dict[str, Any]]) -> None:
        buf = self._buf
        if buf is None:
            buf = self._buf = [None] * self.capacity
        i = self._cursor
        buf[i] = (name, track, ts_ns, dur_ns, depth, args)
        self._cursor = (i + 1) % self.capacity
        self._total += 1

    def span(self, name: str, track: Optional[str] = None, **args):
        """Context manager around its body.  With the ring off it reads
        no clock: it is the profiler's ``TraceMe`` itself, or the shared
        no-op where there is no JAX to mirror to."""
        if self.enabled:
            return _Span(self, name, track, args or None)
        tm = _traceme()
        return tm(name, **args) if tm is not None else _NOOP_SPAN

    def phase(self, name: str, track: Optional[str] = None,
              **args) -> float:
        """Cut between two phases of one thread: end the open phase,
        begin ``name``, return the one ``perf_counter()`` reading."""
        t = self.phase_end()
        sp = self._tls.phase = _Span(self, name, track, args or None)
        sp._begin(int(t * 1e9))
        return t

    def phase_set(self, **args) -> None:
        """Add args to the phase this thread has open."""
        sp = getattr(self._tls, "phase", None)
        if sp is not None:
            sp.set_metadata(**args)

    def phase_end(self, **args) -> float:
        """End the open phase (no-op beyond the clock reading if none
        is open); returns the ``perf_counter()`` reading."""
        t = time.perf_counter()
        tl = self._tls
        sp = getattr(tl, "phase", None)
        if sp is not None:
            tl.phase = None
            if args:
                sp.set_metadata(**args)
            sp._end(int(t * 1e9) if self.enabled else None)
        return t

    def instant(self, name: str, track: Optional[str] = None,
                **args) -> None:
        """Zero-duration marker (request arrivals, evictions, ...);
        ring only."""
        if not self.enabled:
            return
        self._push(name, track or name, time.perf_counter_ns(), -1,
                   self._tls_depth(), args or None)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def events(self, since_ns: Optional[int] = None
               ) -> List[Dict[str, Any]]:
        """Recorded spans, oldest first (wraparound-corrected).
        ``since_ns`` keeps only spans beginning at/after that
        ``perf_counter_ns`` instant — the capture-window export
        (telemetry/profiler.py) uses it to emit just the window."""
        if self._buf is None:
            return []
        n = len(self)
        start = (self._cursor - n) % self.capacity
        out = []
        for k in range(n):
            name, track, ts_ns, dur_ns, depth, args = \
                self._buf[(start + k) % self.capacity]
            if since_ns is not None and ts_ns < since_ns:
                continue
            ev: Dict[str, Any] = {"name": name, "track": track,
                                  "ts_ns": ts_ns, "depth": depth}
            if dur_ns >= 0:
                ev["dur_ns"] = dur_ns
            else:
                ev["instant"] = True
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def chrome_trace(self, process_name: str = "deepspeed_tpu",
                     since_ns: Optional[int] = None) -> Dict:
        """Chrome trace-event JSON object (the ``traceEvents`` array
        format Perfetto and chrome://tracing load).  One tid per track,
        named via thread_name metadata, so each pipeline stage renders
        as its own horizontal track and the dispatch-ahead overlap is
        visually inspectable.  ``since_ns`` restricts the export to
        spans beginning at/after that instant (capture windows)."""
        tids: Dict[str, int] = {}
        trace_events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": process_name}}]
        body: List[Dict[str, Any]] = []
        for ev in self.events(since_ns=since_ns):
            track = ev["track"]
            tid = tids.get(track)
            if tid is None:
                tid = tids[track] = len(tids) + 1
                trace_events.append({
                    "name": "thread_name", "ph": "M", "pid": 1,
                    "tid": tid, "args": {"name": track}})
                # stable top-to-bottom track order in the viewer
                trace_events.append({
                    "name": "thread_sort_index", "ph": "M", "pid": 1,
                    "tid": tid, "args": {"sort_index": tid}})
            rec: Dict[str, Any] = {
                "name": ev["name"], "pid": 1, "tid": tid,
                "ts": ev["ts_ns"] / 1e3,              # microseconds
                "ph": "i" if ev.get("instant") else "X"}
            if not ev.get("instant"):
                rec["dur"] = ev["dur_ns"] / 1e3
            else:
                rec["s"] = "t"                        # thread-scoped
            if ev.get("args"):
                rec["args"] = ev["args"]
            body.append(rec)
        return {"traceEvents": trace_events + body,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": self.dropped}}

    def export_chrome_trace(self, path: str,
                            process_name: str = "deepspeed_tpu") -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(process_name), f)
        return path

    def export_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            for ev in self.events():
                f.write(json.dumps(ev) + "\n")
        return path
