"""Deep-capture windows: bounded ``jax.profiler`` device traces armed
around the next N engine steps (docs/OBSERVABILITY.md "Anomaly
detection & deep capture").

This module is THE gated seam for profiler session control on serving
paths (tpulint's ``profiler-capture`` rule bans direct
``jax.profiler.start_trace``/``stop_trace`` calls inside
serving-loop-marked methods): the engines hold one
:class:`ProfilerCapture` and call ``begin()`` / ``end_step()`` at their
existing step boundaries, and everything session-shaped — the device
trace, the host span window, the clock anchors — happens here, once,
bounded.

The program's spans (``SpanTracer``'s profiler mirror) are INSIDE the
device trace, on its clock: ``tools/tracemerge.py`` takes them from
there.  ``host_trace.json`` and the anchors serve a host-only capture;
the anchor is taken on both sides of ``start_trace`` (which takes
tenths of a second to seconds), so that fallback states how far off it
may be.

A capture window produces one directory::

    <out_dir>/capture_<seq>_<reason>/
        meta.json          clock anchors (perf_ns before and after
                           start_trace, epoch_ns after), step/sid
                           range, reason, profiler presence
        host_trace.json    Chrome trace of the window's host spans
                           (SpanTracer's ring, force-enabled for the
                           window)
        device/            jax.profiler log dir (plugins/profile/...,
                           xplane.pb + trace.json.gz, the ds.* host
                           spans on its /host: plane) — ABSENT when the
                           backend/build has no profiler support
        flight.json        the engine's flight-recorder dump (written
                           by the engine when the window completes)

Degradation is loud but absent: a missing/busy profiler logs a warning
and the window still completes with host spans + meta (tracemerge then
emits a host-only timeline and says so).  Only one jax profiler session
can exist per process — a module-level owner flag keeps two engines
from racing ``start_trace``.

No JAX at import time (the telemetry/ contract); ``jax.profiler`` is
imported inside the capture calls only, and only while a window is
actually starting — a disabled engine never touches this module.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from ..utils.logging import logger

# process-wide session owner: jax.profiler supports ONE active trace
_TRACE_OWNER: List[object] = []


def profiler_available() -> bool:
    """Whether this build exposes ``jax.profiler.start_trace`` (pure
    presence probe — no session is started)."""
    try:
        import jax.profiler
        return hasattr(jax.profiler, "start_trace") \
            and hasattr(jax.profiler, "stop_trace")
    except Exception as e:
        logger.warning("jax.profiler unavailable: %r", e)
        return False


class ProfilerCapture:
    """One engine's capture-window manager.

    States: idle -> ``armed`` (``arm()``) -> ``active`` (``begin()``,
    called by the engine right before its next dispatch) -> idle again
    when ``end_step()`` counts the window down (or ``finish_now()``
    aborts it early on a step failure).  One window at a time; anomaly-
    armed windows (``budgeted=True``) draw from ``max_captures`` until
    ``reset_budget()`` rearms it, explicit ``engine.capture()`` windows
    do not."""

    def __init__(self, out_dir: str, tracer=None,
                 max_captures: Optional[int] = 2):
        self.out_dir = out_dir
        self.tracer = tracer
        self.max_captures = max_captures
        self.captures: List[str] = []     # finished capture dirs
        self._seq = 0
        self._budget_used = 0
        self._armed: Optional[Dict[str, Any]] = None
        self._active: Optional[Dict[str, Any]] = None
        self._warned_unavailable = False

    # ------------------------------------------------------------------
    @property
    def armed(self) -> bool:
        return self._armed is not None

    @property
    def active(self) -> bool:
        return self._active is not None

    def budget_left(self) -> Optional[int]:
        if self.max_captures is None:
            return None
        return max(0, self.max_captures - self._budget_used)

    def reset_budget(self) -> None:
        """Rearm the anomaly-capture budget (``engine.reset_metrics``)."""
        self._budget_used = 0

    # ------------------------------------------------------------------
    def arm(self, steps: int, reason: str = "manual",
            budgeted: bool = False) -> Optional[str]:
        """Schedule a capture of the next ``steps`` engine steps;
        returns the capture directory path, or None when refused (a
        window is already armed/active, or the anomaly budget is
        spent).  Nothing starts until the engine's next step boundary
        calls :meth:`begin`."""
        if self._armed is not None or self._active is not None:
            logger.debug("capture %r refused: a window is already %s",
                         reason, "active" if self._active else "armed")
            return None
        if budgeted:
            left = self.budget_left()
            if left is not None and left <= 0:
                logger.debug("capture %r refused: budget exhausted "
                             "(max_captures=%s)", reason,
                             self.max_captures)
                return None
            self._budget_used += 1
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in reason)[:48]
        cdir = os.path.join(self.out_dir,
                            f"capture_{self._seq:03d}_{safe}")
        self._seq += 1
        self._armed = {"steps": max(1, int(steps)), "reason": reason,
                       "dir": cdir, "budgeted": budgeted}
        return cdir

    def begin(self, sid: Optional[int] = None,
              step: Optional[int] = None) -> None:
        """Start the armed window: create the capture dir, try to start
        the jax profiler session (loudly absent on failure), force the
        span tracer's ring on, and record the clock anchors (both sides
        of ``start_trace``) that a host-only merge falls back on.  Called by the engine at the step boundary BEFORE its
        schedule/stage work, so the window covers whole steps."""
        a, self._armed = self._armed, None
        if a is None:
            return
        cdir = a["dir"]
        try:
            os.makedirs(cdir, exist_ok=True)
        except OSError as e:
            logger.warning("capture dir %r unusable (%s); window "
                           "dropped", cdir, e)
            if a.get("budgeted"):
                # a window that produced NOTHING must not burn the
                # anomaly-capture budget — once the directory is
                # fixed, later anomalies can still capture
                self._budget_used = max(0, self._budget_used - 1)
            return
        profiling = False
        device_dir = os.path.join(cdir, "device")
        t_before_ns = time.perf_counter_ns()
        if _TRACE_OWNER:
            if not self._warned_unavailable:
                self._warned_unavailable = True
                logger.warning(
                    "capture %r: another jax profiler session is "
                    "active — this window records host spans only",
                    a["reason"])
        elif not profiler_available():
            if not self._warned_unavailable:
                self._warned_unavailable = True
                logger.warning(
                    "capture %r: this build exposes no jax profiler — "
                    "recording host spans only", a["reason"])
        else:
            try:
                import jax.profiler
                jax.profiler.start_trace(device_dir)
                _TRACE_OWNER.append(self)
                profiling = True
            except Exception as e:
                # loud-but-absent: the window still completes with host
                # spans + meta; tracemerge reports the device gap
                logger.warning(
                    "capture %r: jax profiler unavailable on this "
                    "backend/build (%s: %s) — recording host spans "
                    "only", a["reason"], type(e).__name__,
                    (str(e).splitlines() or [""])[0][:120])
        tracer_was = None
        if self.tracer is not None:
            tracer_was = self.tracer.enabled
            self.tracer.enable()
        self._active = {
            **a,
            "steps_left": a["steps"],
            "profiling": profiling,
            "device_dir": device_dir if profiling else None,
            "tracer_was_enabled": tracer_was,
            # the session's zero lies between the two readings
            "t_before_start_perf_ns": t_before_ns,
            "t_start_perf_ns": time.perf_counter_ns(),
            "t_start_epoch_ns": time.time_ns(),
            "sid_start": sid,
            "step_start": step,
        }

    def end_step(self, sid: Optional[int] = None,
                 step: Optional[int] = None) -> Optional[str]:
        """Count one completed engine step against the active window;
        finalizes and returns the capture dir when the window is done,
        else None."""
        a = self._active
        if a is None:
            return None
        a["steps_left"] -= 1
        a["sid_end"] = sid
        a["step_end"] = step
        if a["steps_left"] > 0:
            return None
        return self._finish()

    def finish_now(self) -> Optional[str]:
        """Close an active window immediately (the engine calls this on
        a step failure — a capture that witnessed the failure is worth
        more finished than abandoned)."""
        if self._active is None:
            return None
        return self._finish()

    def _finish(self) -> str:
        a, self._active = self._active, None
        t_stop = time.perf_counter_ns()
        if a["profiling"]:
            try:
                import jax.profiler
                jax.profiler.stop_trace()
            except Exception as e:
                logger.warning("capture %r: stop_trace failed (%s)",
                               a["reason"], e)
                a["profiling"] = False
            finally:
                if _TRACE_OWNER and _TRACE_OWNER[-1] is self:
                    _TRACE_OWNER.pop()
        host_trace = None
        if self.tracer is not None:
            try:
                host_trace = os.path.join(a["dir"], "host_trace.json")
                with open(host_trace, "w") as f:
                    json.dump(self.tracer.chrome_trace(
                        since_ns=a["t_start_perf_ns"]), f)
            except OSError as e:
                logger.warning("capture %r: cannot write host trace "
                               "(%s)", a["reason"], e)
                host_trace = None
            if a["tracer_was_enabled"] is False:
                self.tracer.disable()
        meta = {
            "version": 1,
            "reason": a["reason"],
            "steps": a["steps"],
            "t_before_start_perf_ns": a["t_before_start_perf_ns"],
            "t_start_perf_ns": a["t_start_perf_ns"],
            "t_start_epoch_ns": a["t_start_epoch_ns"],
            "t_stop_perf_ns": t_stop,
            "profiler": a["profiling"],
            "device_dir": "device" if a["profiling"] else None,
            "host_trace": "host_trace.json" if host_trace else None,
            "sid_start": a["sid_start"], "sid_end": a.get("sid_end"),
            "step_start": a["step_start"], "step_end": a.get("step_end"),
        }
        try:
            with open(os.path.join(a["dir"], "meta.json"), "w") as f:
                json.dump(meta, f)
        except OSError as e:
            logger.warning("capture %r: cannot write meta (%s)",
                           a["reason"], e)
        self.captures.append(a["dir"])
        logger.info("capture %r complete: %s (device trace: %s)",
                    a["reason"], a["dir"],
                    "yes" if a["profiling"] else "ABSENT")
        return a["dir"]
