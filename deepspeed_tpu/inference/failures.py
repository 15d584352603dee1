"""Failure domains for the serving engine: dispatch watchdog, failure
classification, and fault injection (docs/SERVING.md "Failure domains &
recovery").

PR 6 made the engine survive hostile *traffic*; this module makes it
survive *failures*.  The threat model is this rig's own history — a
backend that hangs 60 s at init, `jax.devices()` dying outright — plus
the classic serving poisons: a request whose batch OOMs the step, an
XLA error that aborts one dispatch, a device call that simply never
returns.  Without supervision any one of those wedges ``generate()``
forever or kills the process; with it, every failure degrades to a
*request-level terminal status* (the new ``failed``), a bounded retry,
or — worst case — a declared-dead engine whose host-side truth a
:meth:`~InferenceEngine.snapshot` carries into a warm restart.

Three pieces, all host-side:

* :class:`Watchdog` — runs a device dispatch/readback on a daemon
  worker thread under a deadline.  Expiry raises
  :class:`DispatchTimeoutError` (the stuck call is abandoned; a fresh
  worker serves the next dispatch, and repeated expiries escalate to
  engine-dead, bounding the leaked-thread count by
  ``FailureConfig.fatal_timeouts``).
* :func:`classify_failure` — THE one classifier seam.  Every broad
  ``except`` on the serving loop routes its exception here (tpulint's
  ``serving-except`` rule enforces it) and acts on the verdict:
  ``RETRY_STEP`` (transient: re-queue the batch, back off),
  ``POISON_STEP`` (deterministic for this batch: re-queue bisected to
  quarantine the poison request), or ``FATAL_ENGINE`` (the device is
  gone: mark the engine dead and raise :class:`EngineDeadError`).
  Exceptions the classifier does not recognize — host-side
  ``ValueError`` / ``KeyError`` / assertion bugs — return ``None`` and
  re-raise: a programming error is not a failure domain.
* :class:`FailurePolicy` — per-engine state: the resolved watchdog
  deadline (``dispatch_timeout_ms``, auto-scaled from the observed
  step latency in the metrics registry), and the fault-injection queue
  the load harness (tools/loadgen.py) and the chaos tests drive the
  whole layer with.

The reference analog is DeepSpeed's elastic-restart loop
(deepspeed/elasticity) at job granularity; a serving engine needs the
same supervision at *step and request* granularity, which is what the
``ROADMAP`` multi-replica router (item 5) and the autotuner's
"survive an OOMing candidate" (item 4, DeepCompile arxiv 2504.09983)
both reduce to.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..telemetry.host import HostSampler, thread_usage, watch_gc
from ..utils.logging import logger

# classifier verdicts (docs/SERVING.md "Failure domains & recovery")
RETRY_STEP = "retry"          # transient: re-queue the batch, back off
POISON_STEP = "poison"        # deterministic for this batch: bisect it
FATAL_ENGINE = "fatal"        # the device is gone: dead + snapshot

# message fragments that mark an XLA/runtime error as a *capacity*
# failure of this batch (the DeepCompile "OOMing candidate"): the step
# is deterministic-bad for this batch shape, so bisect it
_POISON_MARKERS = ("resource_exhausted", "out of memory", "oom",
                   "allocation", "exceeds the memory")
# fragments that mark the backend itself as gone — no batch will ever
# run again on this engine
_FATAL_MARKERS = ("aborted", "data_loss", "device halted", "terminated",
                  "unavailable", "failed to connect", "socket closed",
                  "deadline exceeded for tpu")


# the watchdog worker reads its own usage after a call this long, and
# otherwise this often; the engine's thread likewise (``RoundWatch``)
USAGE_LONG_CALL_S = 0.01
USAGE_PERIOD_S = 0.1


class DispatchTimeoutError(RuntimeError):
    """A guarded device dispatch/readback outlived its deadline."""


class InjectedTimeout(DispatchTimeoutError):
    """A SYNTHETIC watchdog expiry (``inject("timeout")``): raised
    before the guarded call ran, so — unlike a real expiry — the
    dispatch never consumed its donated operands and recovery may keep
    the KV pool.  Classified exactly like the real thing otherwise."""


class EngineDeadError(RuntimeError):
    """The classifier declared the engine unrecoverable: the device (or
    its runtime) is gone.  Host-side truth is intact — callers
    ``snapshot()`` the dead engine and ``InferenceEngine.restore`` the
    work onto a fresh one (the warm-restart loop the load harness
    exercises)."""


class InjectedFault(RuntimeError):
    """A synthetic failure armed via :meth:`FailurePolicy.inject` —
    carries the fault ``kind`` the classifier maps to a verdict, so the
    chaos tests drive the real recovery machinery end-to-end without a
    real broken device."""

    def __init__(self, kind: str, uid: Optional[int] = None):
        super().__init__(f"injected fault: {kind}"
                         + (f" (uid {uid})" if uid is not None else ""))
        self.kind = kind
        self.uid = uid


@dataclasses.dataclass
class FailureConfig:
    """Knobs for the failure-domain layer (``InferenceConfig.failure``).

    The defaults keep the hot path unchanged for short-lived engines:
    the auto watchdog only engages after ``watchdog_warmup_steps``
    observed steps (compiles are unbounded and legitimate), and its
    deadline is generous — operators who want tight hang detection set
    ``dispatch_timeout_ms`` explicitly."""
    # watchdog deadline per guarded device call: a number (ms), "auto"
    # (scaled from the observed mean step latency once warmed up), or
    # None (watchdog off — direct calls, zero thread hops).  A guarded
    # call pays one worker-thread round trip (~40 us measured on a
    # 1-core CPU host) on the dispatch critical path; engines chasing
    # the last fraction of a millisecond per step can set None and
    # keep the classifier/quarantine layer (raised errors still route
    # through it) without deadline supervision
    dispatch_timeout_ms: object = "auto"
    # auto mode: unguarded for the first N steps (compile steps are
    # slow and legitimate), then max(floor, scale x mean step ms)
    watchdog_warmup_steps: int = 8
    auto_timeout_floor_ms: float = 10_000.0
    auto_timeout_scale: float = 50.0
    # consecutive watchdog expiries before the engine is declared dead
    fatal_timeouts: int = 2
    # LIFETIME cap on abandoned watchdog workers: consecutive-expiry
    # escalation resets on every successful step, so a device that
    # hangs intermittently (one expiry every N clean steps) would
    # otherwise strand threads without bound — past this many total
    # abandonments the next expiry is fatal regardless of spacing
    max_abandoned_workers: int = 16
    # consecutive RETRY_STEP failures tolerated before an unrecognized
    # transient error escalates to POISON_STEP (bisect instead of
    # spinning on retries)
    max_step_retries: int = 2
    # times a request may sit in a failing batch before it is closed
    # terminally with status "failed".  A singleton failing batch is
    # proof positive and fails immediately regardless — bisection
    # normally isolates the poison via such a probe; this cap is the
    # safety net for interleavings bisection cannot untangle.  It must
    # exceed ~log2(batch) + 1: an innocent neighbor of a poison request
    # shares its failing probe groups all the way down to the pair
    # split (strikes clear on the innocent's first clean probe)
    poison_strikes: int = 5
    # retry backoff: the scheduler admits nothing for up to this many
    # rounds after a retryable failure (doubling per consecutive
    # failure) — deterministic step-counted backoff, not wall-clock
    max_backoff_rounds: int = 8
    # health(): "degraded" while the last failure is within this many
    # steps (docs/OBSERVABILITY.md health-state table)
    health_window_steps: int = 64
    # post-mortem flight recorder (telemetry/flight.py): directory to
    # auto-dump the black-box JSON into on watchdog expiry, on the
    # fatal engine-dead transition, and on the first healthy->degraded
    # transition of a failure window.  None (default) disables the
    # automatic dumps; ``engine.debug_dump(path)`` works regardless.
    flight_dir: Optional[str] = None

    def __post_init__(self):
        t = self.dispatch_timeout_ms
        if t is not None and t != "auto" \
                and not (isinstance(t, (int, float)) and t > 0):
            raise ValueError(
                f"dispatch_timeout_ms={t!r}: expected a positive ms "
                "value, 'auto', or None")
        if self.fatal_timeouts < 1:
            raise ValueError("fatal_timeouts must be >= 1")
        if self.poison_strikes < 1:
            raise ValueError("poison_strikes must be >= 1")


def classify_failure(exc: BaseException, attempt: int = 0,
                     consecutive_timeouts: int = 0,
                     cfg: Optional[FailureConfig] = None) -> Optional[str]:
    """THE classifier seam: map an exception raised by a guarded device
    dispatch/readback to a verdict — :data:`RETRY_STEP`,
    :data:`POISON_STEP`, :data:`FATAL_ENGINE` — or ``None`` for
    exceptions that are not device failures at all (host programming
    errors re-raise untouched).

    ``attempt``: consecutive failed steps so far (an unrecognized
    transient escalates retry -> poison after ``max_step_retries``).
    ``consecutive_timeouts``: watchdog expiries in a row (escalate to
    fatal after ``fatal_timeouts`` — a device that repeatedly outlives
    a generous deadline is gone, and each expiry leaks one abandoned
    worker thread)."""
    cfg = cfg or FailureConfig()
    if isinstance(exc, InjectedFault):
        return {"crash": POISON_STEP, "oom": POISON_STEP,
                "transient": RETRY_STEP,
                "fatal": FATAL_ENGINE}.get(exc.kind, POISON_STEP)
    if isinstance(exc, DispatchTimeoutError):
        return FATAL_ENGINE if consecutive_timeouts >= cfg.fatal_timeouts \
            else RETRY_STEP
    # device/runtime errors: XlaRuntimeError and friends all derive from
    # jax's JaxRuntimeError umbrella; classify by message
    try:
        import jax
        device_error = isinstance(exc, jax.errors.JaxRuntimeError)
    except Exception:  # tpulint: disable=silent-except — jax-free probe
        device_error = False
    if not device_error:
        return None
    msg = str(exc).lower()
    if any(m in msg for m in _FATAL_MARKERS):
        return FATAL_ENGINE
    if any(m in msg for m in _POISON_MARKERS):
        return POISON_STEP
    return RETRY_STEP if attempt < cfg.max_step_retries else POISON_STEP


class Watchdog:
    """Deadline supervision for blocking device calls.

    One daemon worker thread runs the guarded callable; the caller
    waits on a result queue with a timeout.  Expiry raises
    :class:`DispatchTimeoutError` and ABANDONS the worker (a stuck XLA
    call cannot be interrupted from Python) — the next guarded call
    gets a fresh worker, a poison pill makes the abandoned one exit as
    soon as its stuck call completes, and the engine's
    ``fatal_timeouts`` / ``max_abandoned_workers`` escalations bound
    how many threads a dying device can strand.  With
    ``timeout_ms=None`` the call runs inline: zero threads, zero hops —
    the watchdog costs nothing unless a deadline is actually set.

    The hand-off is stamped, always: four ``perf_counter`` readings per
    guarded call — put on the request queue, taken by the worker,
    ``fn()`` returned, result taken by the caller.  They are handed back
    through the caller's ``stamps`` dict (left empty by an inline call):
    ``queued_us``, ``fn_us``, ``taken_us``, and the two hand-overs as
    ``hop_us`` (what the thread hops cost the step).  After a call that
    lasted over ``USAGE_LONG_CALL_S``, and otherwise once every
    ``USAGE_PERIOD_S``, the worker also reads its own ``getrusage`` (a
    system call, so not every call): ``cpu_us``, ``vcsw`` and ``ivcsw``
    are then its rise since its previous reading, which is this call's
    and the short ones' before it (the worker only sleeps on its queue
    between calls).  The watchdog judges none of
    them: a round that ran long is the engine's to note, once, with
    these beside its own cuts (:class:`RoundWatch`, ``slow_round``) — a
    completion the runtime delivered late reads there as a long
    ``fn()``.  An abandoned worker whose call finally returns hands
    ``on_note`` one ``guard_late_return`` record: a result the hand-off
    lost reads there as a short ``fn()`` whose result the caller never
    took."""

    def __init__(self, on_note: Optional[Callable[..., None]] = None):
        self._req: Optional[queue.Queue] = None
        self._res: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._token = 0
        self.abandoned = 0          # workers stranded by expiries
        self.on_note = on_note      # (kind, **stamps) -> None
        self.worker_tid = 0         # the live worker's native thread id
        # ONE guarded call at a time: the worker handshake is a single
        # (req, res) queue pair, so two concurrent run() calls would
        # interleave tokens on one queue, and a shared expiry could
        # tear down (_thread = _req = _res = None) the very worker the
        # other caller is still waiting on — double-counting
        # ``abandoned`` and stranding a result.  The admission lock
        # makes spawn + token bump + wait + abandon one atomic episode.
        # Reentrant: run() holds it across its call into
        # _ensure_worker(), which takes it again for callers that
        # pre-warm the worker directly.
        self._admit = threading.RLock()

    def _note(self, kind: str, **info) -> None:
        if self.on_note is not None:
            self.on_note(kind, **info)

    def _ensure_worker(self) -> None:
        with self._admit:
            if self._thread is not None and self._thread.is_alive():
                return
            self._req = queue.Queue()
            self._res = queue.Queue()

            def loop(req: queue.Queue, res: queue.Queue) -> None:
                # one int, published by the worker for the slow path's
                # schedstat read; a stale one names a dead thread's file
                self.worker_tid = threading.get_native_id()  # tpulint: disable=shared-state-race
                cpu0, vcsw0, ivcsw0 = thread_usage()
                t_read = time.perf_counter()
                while True:
                    token, fn, call = req.get()
                    if fn is None:    # poison pill: worker was abandoned
                        return
                    t_taken = time.perf_counter()
                    try:
                        ok, val = True, fn()
                    except BaseException as e:  # tpulint: disable=silent-except — shipped across the queue and re-raised in the caller
                        ok, val = False, e
                    t_ret = time.perf_counter()
                    usage = None
                    if t_ret - t_taken > USAGE_LONG_CALL_S \
                            or t_ret - t_read > USAGE_PERIOD_S:
                        # a system call (6 us on the chip's host): for a
                        # call that was long, else ten times a second
                        cpu, vcsw, ivcsw = thread_usage()
                        usage = (cpu - cpu0, vcsw - vcsw0, ivcsw - ivcsw0)
                        cpu0, vcsw0, ivcsw0, t_read = cpu, vcsw, ivcsw, t_ret
                    res.put((token, ok, val, t_taken, t_ret, usage))
                    if call.get("abandoned"):
                        # nobody will take this result: say when the
                        # stuck call did come back
                        self._note(
                            "guard_late_return", site=call["site"],
                            sid=call["sid"], ok=ok,
                            deadline_ms=call["deadline_ms"],
                            queued_ms=(t_taken - call["t_put"]) * 1e3,
                            fn_ms=(t_ret - t_taken) * 1e3,
                            late_ms=(t_ret - call["t_put"]) * 1e3
                            - call["deadline_ms"])

            self._thread = threading.Thread(
                target=loop, args=(self._req, self._res),
                name="serving-watchdog", daemon=True)
            self._thread.start()

    def run(self, fn: Callable, timeout_ms: Optional[float],
            site: Optional[str] = None, sid: Optional[int] = None,
            stamps: Optional[Dict[str, float]] = None):
        """Run ``fn()`` under ``timeout_ms``; inline when None.
        ``site``/``sid`` name the call in a late-return record;
        ``stamps``, the caller's own dict, receives the hand-off's
        stamps and the worker's usage (the class docstring)."""
        if timeout_ms is None:
            return fn()
        with self._admit:
            self._ensure_worker()
            self._token += 1
            token = self._token
            t_put = time.perf_counter()
            call = {"site": site, "sid": sid, "t_put": t_put,
                    "deadline_ms": timeout_ms}
            self._req.put((token, fn, call))
            deadline = t_put + timeout_ms / 1e3
            while True:
                remaining = deadline - time.perf_counter()
                try:
                    tok, ok, val, t_taken, t_ret, usage = self._res.get(
                        timeout=max(1e-4, remaining)
                        if remaining > 0 else 1e-4)
                except queue.Empty:
                    # abandon this worker.  A stuck XLA call cannot be
                    # interrupted from Python, but the poison pill makes
                    # the thread EXIT (instead of parking forever) the
                    # moment the call eventually completes — only calls
                    # that truly never return keep a thread, and the
                    # engine's max_abandoned_workers cap declares the
                    # device dead before that count can grow unboundedly
                    self.abandoned += 1
                    call["abandoned"] = True
                    self._req.put((None, None, None))
                    self._thread = self._req = self._res = None
                    raise DispatchTimeoutError(
                        f"device dispatch outlived its {timeout_ms:.0f} ms "
                        "deadline") from None
                if tok != token:    # stale result from an older call
                    continue
                if stamps is not None:
                    t_got = time.perf_counter()
                    queued = (t_taken - t_put) * 1e6
                    taken = (t_got - t_ret) * 1e6
                    stamps["hop_us"] = queued + taken
                    stamps["queued_us"] = queued
                    stamps["fn_us"] = (t_ret - t_taken) * 1e6
                    stamps["taken_us"] = taken
                    if usage is not None:
                        stamps["cpu_us"] = usage[0] * 1e6
                        stamps["vcsw"], stamps["ivcsw"] = usage[1:]
                if ok:
                    return val
                raise val


# ---- a round that ran long (docs/OBSERVABILITY.md "A slow round") ------
SLOW_ROUND = "slow_round"
# a round is slow where it lasts over FACTOR x the running mean round AND
# over that mean plus MARGIN: a long prefill among decodes is neither
SLOW_ROUND_FACTOR = 2.0
SLOW_ROUND_MARGIN_MS = 50.0
SLOW_ROUND_LOG_PERIOD_S = 1.0
_ROUND_ALPHA = 0.05                 # the running means' weight of a round
_PHASES = ("outside", "schedule", "stage", "dispatch", "wait", "readback",
           "emit")
# the phases in which the engine's thread has work of its own to run
_HOST_PHASES = ("schedule", "stage", "readback", "emit")


def slow_round_where(rec: Dict) -> Tuple[str, Optional[str]]:
    """THE rule table: where a slow round's time went, from the numbers
    of its record (:class:`RoundWatch` writes them; a number the record
    lacks reads 0).  First match wins.  ``E`` is the round's excess over
    the mean round.  The CPU readings cover ``host_window_ms``, which
    ends with the round and began ``usual = host_window_ms - E`` of
    ordinary running before the excess, so ``own = thread_cpu_ms -
    thread_cpu_rate x usual`` is what the engine's thread burned in the
    excess and ``extra = process_cpu_ms - thread_cpu_ms - other_cpu_rate
    x host_window_ms`` what the OTHER threads burned beyond their usual
    share of the whole window:

    ====================  ==============================================
    ``descheduled``       the host says so: throttled time rose by E/4;
                          or run delay or CPU pressure did, and a thread
                          of the loop was preempted in the window (or
                          the previous reading is no older than ten such
                          rounds)
    ``interpreter``       collections ended in the round took E/2
                          (``by=gc``); or ``extra`` is 0.4 E while
                          ``own`` is under E/4: ``by=loop`` where the
                          event loop's heartbeat was E/2 late, else
                          ``by=thread``
    ``descheduled``       nobody ran: ``extra`` under 0.4 E, and the
                          event loop's heartbeat was E/2 late too (two
                          threads lost the same time and no one burned
                          it), or the phase below is a host phase of the
                          engine in which ``own`` is under E/4 (a thread
                          with work to do that did not run)
    ``outside``           the longest phase over its mean, holding
    ``host:<phase>``      0.4 E, is the time between two ``step`` calls
    ``handoff``           / a host phase of the engine / the launch or
    ``completion``        the wait: ``handoff`` where ``queued`` +
    ``device``            ``taken_back`` hold E/2; a launch otherwise is
                          ``host:dispatch``; a wait with the process
                          idle is ``completion`` where the next launch's
                          samples were ready (the runtime delivered
                          late), ``device`` where not or none was behind
    ``unknown``           anything else
    ====================  ==============================================

    Returns ``(where, by)``; ``by`` is None outside ``interpreter``."""
    g = lambda k: rec.get(k) or 0.0          # noqa: E731
    excess = g("round_ms") - g("mean_ms")
    if excess <= 0.0:
        return "unknown", None
    recent = g("since_s") * 1e3 <= 10.0 * g("round_ms")
    if g("throttled_ms_rise") >= excess / 4 or (
            (g("ivcsw") + g("worker_ivcsw") >= 1 or recent)
            and max(g("run_delay_ms_rise"),
                    g("pressure_ms_rise")) >= excess / 4):
        return "descheduled", None
    if g("gc_ms") >= excess / 2:
        return "interpreter", "gc"
    window = max(g("host_window_ms"), g("round_ms"))
    own = g("thread_cpu_ms") - g("thread_cpu_rate") * (window - excess)
    busy = g("process_cpu_ms") - g("thread_cpu_ms") \
        - g("other_cpu_rate") * window >= 0.4 * excess
    if busy and own <= excess / 4:
        return "interpreter", \
            "loop" if g("loop_lag_ms") >= excess / 2 else "thread"
    means = rec.get("phase_mean_ms") or {}
    over = {p: g(p + "_ms") - (means.get(p) or 0.0) for p in _PHASES}
    phase = max(over, key=over.get)
    if over[phase] < 0.4 * excess:
        phase = None
    if not busy and (g("loop_lag_ms") >= excess / 2 or (
            phase in _HOST_PHASES and own <= excess / 4)):
        return "descheduled", None
    if phase is None:
        return "unknown", None
    if phase == "outside":
        return "outside", None
    if phase == "dispatch":
        hand = g("launch_queued_ms") + g("launch_taken_back_ms")
        return ("handoff" if hand >= excess / 2 else "host:dispatch"), None
    if phase != "wait":
        return "host:" + phase, None
    if g("queued_ms") + g("taken_back_ms") >= excess / 2:
        return "handoff", None
    if busy:
        return "unknown", None
    return ("completion" if rec.get("next_ready") else "device"), None


class RoundWatch:
    """The served loop's own stall record: one ``slow_round`` a round
    that ran long, with where it went.

    A round is one ``InferenceEngine.step()`` on the engine's thread,
    from the previous ``step``'s return (where that left a launch in
    flight; from this call's first cut where the engine had been idle)
    to this one's.  Its cuts are the readings ``tracer.phase()`` already
    hands the engine (:meth:`cut_dispatch`, :meth:`cut_collect`: the
    last round's, in these slots); :meth:`end` adds the round's ONE own
    ``perf_counter`` reading.  The host's state costs system calls
    (``getrusage``, ``process_time``: 6 us each on the chip's host,
    where ``perf_counter`` is 0.07), so it is read at a round's end only
    once every ``USAGE_PERIOD_S`` and when a round IS slow: a slow
    round's CPU and switches are the rise since the last such reading
    (``host_window_ms``), and the running rates (CPU ms a wall ms, of
    this thread and of the process's others) say what part of it is the
    ordinary running before the excess.  Everything else
    (``HostSampler``'s files, the collections' ring, the rule table) is
    touched only once a round is slow.  No file, no lock, nothing that
    grows.

    The means are exponential (weight ``_ROUND_ALPHA``, plain means
    until that bites); a slow round enters the round's clipped to the
    threshold, and the rates not at all.  No round is judged before
    ``warm`` rounds have entered, none that compiled, failed or was read
    back outside ``step``."""

    def __init__(self, warm: int, timings, note: Callable[..., None],
                 watchdog: Optional[Watchdog] = None, metrics=None,
                 tracer=None):
        self.warm = max(1, int(warm))
        self._timings = timings
        self._note = note
        self._watchdog = watchdog
        self.sampler = HostSampler()
        self.gc = watch_gc(tracer)
        self._tid = 0               # the engine thread's native id
        self._c_rounds = self._c_seconds = None
        if metrics is not None:
            self._c_rounds = metrics.counter(
                "serving_slow_rounds_total",
                "served rounds that lasted over twice the mean round and "
                "over it plus 50 ms, by where the time went (where: "
                "device|completion|descheduled|interpreter|handoff|"
                "host:<phase>|outside|unknown)", int_valued=True)
            self._c_seconds = metrics.counter(
                "serving_slow_round_seconds_total",
                "seconds the slow rounds lasted beyond the mean round "
                "(what the loop lost to them), by where")
        # the previous round's end, and was a launch left in flight
        self.t_end = 0.0
        self.ahead = False
        self.gc_s = 0.0
        # the event loop's worst heartbeat lateness since then, and when
        # its next beat is due on ``time.monotonic`` (0.0: no loop);
        # written by the loop's thread, ``InferenceEngine.note_loop_lag``
        self.lag_ms = 0.0
        self.beat_due = 0.0
        # the engine thread's last reading of the host: when, its CPU
        # and switches, the process's CPU; None until its first
        self.t_host = 0.0
        self._host: Optional[Tuple[float, int, int, float]] = None
        self.reset()

    def reset(self) -> None:
        """Forget the means (``reset_metrics()``: the timed region's
        rounds are not the warm-up's) and take the sampler's baseline."""
        self.n = self.n_rates = 0
        self.mean_ms = 0.0
        self.thread_rate = self.other_rate = 0.0
        self.limit_us = float("inf")    # a wait this long is a slow round
        self._host = None
        self._clear()
        self.void = True                # the round under way is cut
        self.sampler.read()

    def _clear(self) -> None:
        self.c0 = 0.0               # this round's first cut; 0.0: none
        self.void = False
        self.sid = 0
        self.sched_ms = self.stage_ms = self.disp_ms = 0.0
        self.wait_ms = self.read_ms = 0.0
        self.t_read = 0.0           # the readback's end
        self.launch: Optional[Dict[str, float]] = None
        self.wait: Optional[Dict[str, float]] = None
        self.next_ready: Optional[bool] = None
        self.rec: Optional[Dict] = None     # judged at the wait's end

    # ---- the engine's cuts (no clock is read here) ---------------------
    def cut_dispatch(self, t0: float, t1: float, t2: float, t3: float,
                     cold: bool, stamps: Dict[str, float]) -> None:
        if not self.c0:
            self.c0 = t0
        self.sched_ms = (t1 - t0) * 1e3
        self.stage_ms = (t2 - t1) * 1e3
        self.disp_ms = (t3 - t2) * 1e3
        self.launch = stamps
        if cold:
            self.void = True

    def cut_collect(self, sid: int, t0: float, t1: float, t2: float,
                    cold: bool, stamps: Dict[str, float]) -> None:
        if not self.c0:
            self.c0 = t0
        self.sid = sid
        self.wait_ms = (t1 - t0) * 1e3
        self.read_ms = (t2 - t1) * 1e3
        self.t_read = t2
        self.wait = stamps
        if cold:
            self.void = True

    # ---- the round's end ----------------------------------------------
    def end(self, ahead: bool) -> None:
        """``step`` returns: close the round, judge it, roll the slots.
        ``ahead``: a launch is left in flight, so the driver comes
        straight back and the time until it does is the next round's."""
        t = time.perf_counter()
        c0 = self.c0
        if c0 and not self.void:
            start = self.t_end if self.ahead else c0
            dur = (t - start) * 1e3
            mean = self.mean_ms
            # the threshold the rounds so far left: inf before ``warm``
            limit = self.limit_us * 1e-3
            if dur > limit or self.rec is not None:
                self._slow(t, start, dur)
                dur = min(dur, limit)
            n = self.n + 1
            self.n = n
            mean += (dur - mean) * max(_ROUND_ALPHA, 1.0 / n)
            self.mean_ms = mean
            if n >= self.warm:
                self.limit_us = 1e3 * max(SLOW_ROUND_FACTOR * mean,
                                          mean + SLOW_ROUND_MARGIN_MS)
        if t - self.t_host >= USAGE_PERIOD_S:
            self._read_host(t, rates=True)
        self.t_end, self.ahead = t, ahead
        self.gc_s = self.gc.total_s
        self.lag_ms = 0.0
        self._clear()

    def _read_host(self, t: float, rates: bool = False
                   ) -> Tuple[float, float, int, int, float]:
        """Read this thread's usage and the process's CPU (three system
        calls), keep them as the next reading's baseline, and return the
        rise since the previous one: ``(seconds, thread CPU s, voluntary,
        involuntary switches, process CPU s)``.  ``rates``: an ordinary
        stretch, which enters the running rates."""
        cpu, vcsw, ivcsw = thread_usage()
        proc = time.process_time()
        prev, dt = self._host, t - self.t_host
        self._host, self.t_host = (cpu, vcsw, ivcsw, proc), t
        if prev is None:
            return 0.0, 0.0, 0, 0, 0.0
        rise = (dt, cpu - prev[0], vcsw - prev[1], ivcsw - prev[2],
                proc - prev[3])
        if rates and dt > 0.0:
            self.n_rates += 1
            a = max(_ROUND_ALPHA, 1.0 / self.n_rates)
            self.thread_rate += (rise[1] / dt - self.thread_rate) * a
            self.other_rate += ((rise[4] - rise[1]) / dt
                                - self.other_rate) * a
        return rise

    # ---- the slow path -------------------------------------------------
    def judge_wait(self, sid: int, stamps: Dict[str, float],
                   next_ready: Optional[bool]) -> str:
        """A guarded wait that alone outlasts the threshold
        (``limit_us``): the verdict is reached HERE, from the stamps just
        taken back, while the wait's phase is still open for the caller
        to mark with it; :meth:`end` completes the record."""
        t = time.perf_counter()
        start = self.t_end if self.ahead else (self.c0 or t)
        self.sid, self.next_ready = sid, next_ready
        wait_ms = (stamps["queued_us"] + stamps["fn_us"]
                   + stamps["taken_us"]) / 1e3
        self.wait, self.wait_ms = stamps, wait_ms
        if not self.c0:
            self.c0 = t - wait_ms / 1e3
        self.rec = self._measure(t, start, (t - start) * 1e3)
        return self.rec["where"]

    def _measure(self, t: float, start: float, dur: float) -> Dict:
        """The record of a round that lasted ``dur`` ms up to ``t``."""
        if not self._tid:
            self._tid = threading.get_native_id()
        tm = self._timings
        steps = max(float(tm["steps"]), 1.0)
        means = {p: round(float(tm[k]) / steps, 3) for p, k in (
            ("schedule", "schedule_ms"), ("stage", "stage_ms"),
            ("dispatch", "device_ms"), ("wait", "wait_ms"),
            ("readback", "readback_ms"))}
        outside = (self.c0 - self.t_end) * 1e3 \
            if self.ahead and self.c0 else 0.0
        rec: Dict = {
            "sid": self.sid, "round_ms": dur, "mean_ms": self.mean_ms,
            "t_s": t, "outside_ms": outside,
            "schedule_ms": self.sched_ms, "stage_ms": self.stage_ms,
            "dispatch_ms": self.disp_ms, "wait_ms": self.wait_ms,
            "readback_ms": self.read_ms,
            "emit_ms": (t - self.t_read) * 1e3 if self.t_read else 0.0,
            "phase_mean_ms": means}
        for stamps, pre in ((self.launch, "launch_"), (self.wait, "")):
            if stamps:
                rec[pre + "queued_ms"] = stamps["queued_us"] / 1e3
                rec[pre + "fn_ms"] = stamps["fn_us"] / 1e3
                rec[pre + "taken_back_ms"] = stamps["taken_us"] / 1e3
        if self.next_ready is not None:
            rec["next_ready"] = self.next_ready
        dt, cpu, vcsw, ivcsw, proc = self._read_host(t)
        lag = self.lag_ms
        if self.beat_due:
            # a beat that was due and has not run yet is late by now: a
            # loop that stood still with this thread reports only after
            lag = max(lag, (time.monotonic() - self.beat_due) * 1e3)
        worker = [s for s in (self.launch, self.wait)
                  if s and "cpu_us" in s]
        rec.update(
            host_window_ms=dt * 1e3, thread_cpu_ms=cpu * 1e3,
            process_cpu_ms=proc * 1e3, vcsw=vcsw, ivcsw=ivcsw,
            thread_cpu_rate=self.thread_rate,
            other_cpu_rate=self.other_rate,
            worker_cpu_ms=sum(s["cpu_us"] for s in worker) / 1e3,
            worker_vcsw=sum(s["vcsw"] for s in worker),
            worker_ivcsw=sum(s["ivcsw"] for s in worker),
            gc_ms=(self.gc.total_s - self.gc_s) * 1e3, loop_lag_ms=lag)
        gcs = self.gc.ended_in(start, t)
        if gcs:
            rec["gc"] = gcs[-8:]
        wd = self._watchdog
        rec.update(self.sampler.read(
            (self._tid, wd.worker_tid if wd is not None else 0)))
        where, by = slow_round_where(rec)
        rec["where"] = where
        if by is not None:
            rec["by"] = by
        return rec

    def _slow(self, t: float, start: float, dur: float) -> None:
        rec = self.rec
        if rec is None:
            rec = self._measure(t, start, dur)
        else:
            # judged at the wait's end: the rest of the round is added
            rec.update(round_ms=dur, t_s=t, wait_ms=self.wait_ms,
                       readback_ms=self.read_ms,
                       emit_ms=(t - self.t_read) * 1e3
                       if self.t_read else 0.0)
        where = rec["where"]
        lost = (dur - rec["mean_ms"]) / 1e3
        if self._c_rounds is not None:
            self._c_rounds.inc(where=where)
            self._c_seconds.inc(lost, where=where)
        self._note(SLOW_ROUND, **{
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in rec.items()})


class FailurePolicy:
    """Per-engine failure-domain state: the resolved watchdog deadline
    and the fault-injection queue.  The ENGINE owns the recovery
    bookkeeping (strikes, probe groups, backoff — it owns the state
    those mutate); this object owns what is independent of it."""

    def __init__(self, cfg: FailureConfig, timings, flight=None,
                 metrics=None, tracer=None):
        """``timings``: the engine's counter view — the auto deadline
        reads observed ``device_ms + wait_ms`` per step from it (the
        PR-5 metrics registry is the measurement substrate).
        ``flight``: the engine's flight recorder; the slow-round and
        late-return records go there and to the log.  ``metrics`` and
        ``tracer``: the engine's registry (the slow rounds' counters)
        and span tracer (its ring takes the collections' spans)."""
        self.cfg = cfg
        self._timings = timings
        self._flight = flight
        self.watchdog = Watchdog(on_note=self._guard_note)
        self.rounds = RoundWatch(cfg.watchdog_warmup_steps, timings,
                                 self._guard_note, self.watchdog,
                                 metrics=metrics, tracer=tracer)
        # at most one slow_round line a second reaches the log
        self._log_after = 0.0
        self._log_held = 0
        # armed injections, consumed in order by guarded dispatches:
        # (kind, uid filter or None, remaining fire count)
        self._inject: List[Tuple[str, Optional[int], int]] = []

    def _guard_note(self, kind: str, **info) -> None:
        """One flight-recorder breadcrumb per slow round or late guarded
        call, and one log line: a late return always (it may run on the
        abandoned worker's thread), a slow round at most once a second,
        the next line counting those held back (``held=``)."""
        if self._flight is not None:
            self._flight.note(kind, **info)
        if kind == SLOW_ROUND:
            now = time.perf_counter()
            if now < self._log_after:
                self._log_held += 1
                return
            self._log_after = now + SLOW_ROUND_LOG_PERIOD_S
            info["held"], self._log_held = self._log_held, 0
        logger.warning("%s: %s", kind, " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in info.items()))

    # ---- fault injection (the chaos harness seam) ---------------------
    def inject(self, kind: str, uid: Optional[int] = None,
               n: int = 1) -> None:
        """Arm ``n`` firings of a synthetic fault, consumed by guarded
        dispatches.  ``kind``: ``crash``/``oom`` (classified
        poison-for-step), ``transient`` (retryable), ``fatal``
        (engine-dead), ``timeout`` (a deterministic watchdog expiry —
        no real sleeping), or ``hang`` (a real sleep longer than the
        deadline, driving the real watchdog thread).  With ``uid``,
        the fault only fires on a batch containing that uid (a
        *poison request*: every batch it sits in fails, which is what
        the bisection quarantine isolates)."""
        self._inject.append((kind, uid, n))

    def _take_injection(self, uids) -> Optional[str]:
        for i, (kind, uid, n) in enumerate(self._inject):
            if uid is not None and uid not in uids:
                continue
            if n <= 1:
                del self._inject[i]
            else:
                self._inject[i] = (kind, uid, n - 1)
            return kind
        return None

    # ---- the guarded-call entry --------------------------------------
    def run(self, fn: Callable, uids=(), cold: bool = False,
            site: Optional[str] = None, sid: Optional[int] = None,
            stamps: Optional[Dict[str, float]] = None):
        """Run one guarded device call: consume any armed injection,
        then execute under the current watchdog deadline.  ``site``
        (``dispatch``/``collect``) and ``sid`` name the call
        in the watchdog's late-return record; ``stamps`` receives the
        hand-off's stamps (``Watchdog.run``).  ``cold``
        marks a call whose compiled program has never completed before
        (a compile may ride it): it runs UNGUARDED — compiles are slow
        and legitimate, and abandoning a worker mid-XLA-compile leaves
        native code running on a thread the interpreter cannot join
        (measured: segfault at process exit).  The deadline therefore
        supervises steady-state dispatches only, which is where a hang
        means a sick device rather than a working compiler."""
        kind = self._take_injection(uids)
        if kind is not None:
            if kind == "timeout":
                raise InjectedTimeout("injected watchdog expiry")
            if kind == "hang":
                # a real stall: the real watchdog must catch it
                inner = fn

                def fn():
                    time.sleep((self.deadline_ms() or 50.0) * 4 / 1e3)
                    return inner()
            else:
                raise InjectedFault(kind, uid=None)
        return self.watchdog.run(fn,
                                 None if cold else self.deadline_ms(),
                                 site=site, sid=sid, stamps=stamps)

    def deadline_ms(self) -> Optional[float]:
        """The current watchdog deadline: the configured value, or the
        auto-scaled one — ``max(floor, scale x mean observed step
        ms)`` once ``watchdog_warmup_steps`` steps calibrated it (the
        warmup steps run unguarded: compiles are slow and legitimate,
        and short unit-test engines never pay the thread hop)."""
        t = self.cfg.dispatch_timeout_ms
        if t is None:
            return None
        if t != "auto":
            return float(t)
        tm = self._timings
        steps = int(tm["steps"])
        if steps < self.cfg.watchdog_warmup_steps:
            return None
        mean_ms = (float(tm["device_ms"]) + float(tm["wait_ms"])) \
            / max(steps, 1)
        return max(self.cfg.auto_timeout_floor_ms,
                   self.cfg.auto_timeout_scale * mean_ms)


def bisect_groups(uids: List[int]) -> List[List[int]]:
    """Split a failing batch's uids into the two probe halves the
    quarantine schedules next (docs/SERVING.md: the bisection rule).
    Singleton batches don't bisect — a singleton failure is proof."""
    if len(uids) <= 1:
        return []
    mid = len(uids) // 2
    return [uids[:mid], uids[mid:]]
