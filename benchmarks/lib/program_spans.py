"""What the program says about itself inside a profiler trace: its own
host spans (``ds.<layer>.<phase>``, written by ``telemetry/tracer.py``
as ``TraceMe`` events onto the ``/host:`` planes, on the clock of the
device's ``XLA Ops`` lines) and the ``jax.named_scope`` names in its
operations' JAX paths.

Two reductions, both of device 0 of the traced window (the drivers'
``bench.trace.window`` span, to which every operation is clipped as in
``trace.reduce``; first operation to last in a file without one):

* **idle time by what the host was doing.**  Every idle interval of the
  device inside the window (the gaps of ``trace.py``'s busy union, the
  stretches from the window's edges to the first and from the last
  operation included) is cut at span edges, and each piece goes to the
  innermost ``ds.*`` span open at that time: the engine's thread first,
  then the event loop's ``ds.gateway.route``.  A piece under no span is
  ``handoff`` when it lies between one ``ds.gateway.pump``'s end and the
  next one's start (the thread hops and the loop's other work), else
  ``unattributed`` (the window's edges).  Booked by overlap, not by the
  gap's midpoint: a gap that spans three phases is split among them.
* **device time by named scope**: each operation's time goes to the
  first of the program's scopes (``qkv``, ``kv_write``, ``attn``,
  ``attn_out``, ``ffn``, ``unembed``, ``sample``) that its JAX path
  holds; containers (``while``, ``call``) hold their bodies' events and
  are left out.

A trace without a ``ds.*`` event (the parent of the PR that added them,
a train cell, a rehearsal on the CPU) gives ``None``: the readers then
report nothing.
"""

import bisect

from benchmarks.lib import trace
from benchmarks.lib.common import note

PREFIX = "ds."
PUMP, ROUTE, APPLY = "ds.gateway.pump", "ds.gateway.route", "ds.gateway.apply"
WAIT = "ds.serve.wait"
LAUNCH = ("ds.serve.dispatch", "ds.serve.compile")
# the engine's own host phases of a step (engine.timings' schedule_ms,
# stage_ms, readback_ms, and the launch), with the spans nested in them
ENGINE_HOST = ("ds.serve.schedule", "ds.serve.stage", "ds.serve.dispatch",
               "ds.serve.readback", "ds.serve.prefix_match",
               "ds.serve.cow_drain", "ds.serve.tier_demote",
               "ds.serve.tier_restage")
SCOPES = ("qkv", "kv_write", "attn_out", "attn", "ffn", "unembed", "sample")


def read(path: str):
    """({thread: [(start_s, end_s, name, stats)]}, device 0's operations
    [(start_s, end_s, text)], instruction name -> JAX paths): the
    ``ds.*`` events of the host planes by thread line, with their stats,
    beside what ``trace.read`` takes from the same file."""
    from jax.profiler import ProfileData
    threads = {}
    for pi, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            evs = [(ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9, ev.name,
                    dict(ev.stats))
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                threads[(pi, li)] = evs
    t = trace.read(path)
    devs = t["devices"]
    return threads, (devs[min(devs)] if devs else []), t["op_names"]


def _innermost(spans, t):
    """Name of the shortest span of one thread that holds ``t`` (spans of
    one thread nest), or None."""
    best = None
    for s, e, name, _ in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best and best[1]


def book_idle(threads: dict, ops: list, window=None) -> dict:
    """Device 0's idle seconds inside ``window`` (``(lo, hi)`` on the
    trace's clock; first operation to last without one) by span, cut at
    span edges.

    ``{"idle_s", "window": (lo, hi), "by_span": {name | "handoff" |
    "unattributed": s}}``"""
    if window:
        ops = trace.clip(ops, window)
    merged = trace._union([(s, e) for s, e, _ in ops])
    lo, hi = window or (merged[0][0], merged[-1][1])
    engine = [sp for evs in threads.values()
              if any(nm != ROUTE for _, _, nm, _ in evs) for sp in evs]
    loop = [sp for evs in threads.values()
            if all(nm == ROUTE for _, _, nm, _ in evs) for sp in evs]
    pumps = sorted((s, e) for s, e, nm, _ in engine if nm == PUMP)
    pump_starts = [s for s, _ in pumps]
    pump_ends = sorted(e for _, e in pumps)

    def label(t):
        name = _innermost(engine, t) or _innermost(loop, t)
        if name:
            return name
        after = bisect.bisect_right(pump_ends, t) > 0
        before = bisect.bisect_left(pump_starts, t) < len(pump_starts)
        return "handoff" if after and before else "unattributed"

    cuts = sorted({lo, hi} | {x for s, e, _, _ in engine + loop
                              for x in (s, e) if lo < x < hi})
    labels = [label((a + b) / 2) for a, b in zip(cuts, cuts[1:])]
    by_span, idle = {}, 0.0
    edges = [(lo, lo)] + merged + [(hi, hi)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        idle += s1 - e0
        i = bisect.bisect_right(cuts, e0) - 1
        while i < len(labels) and cuts[i] < s1:
            piece = min(s1, cuts[i + 1]) - max(e0, cuts[i])
            if piece > 0:
                by_span[labels[i]] = by_span.get(labels[i], 0.0) + piece
            i += 1
    return {"idle_s": idle, "window": (lo, hi), "by_span": by_span}


def scope_of(paths) -> str:
    """The first of the program's scopes that one of an operation's JAX
    paths holds as a whole component, or None."""
    for p in paths:
        if p.startswith("@"):
            continue
        parts = p.split("/")
        for scope in SCOPES:
            if scope in parts:
                return scope
    return None


def book_scopes(ops: list, op_names: dict) -> dict:
    """Device 0's operation seconds by named scope (``"none"`` for an
    operation under no scope), containers left out; its busy union; and
    the unscoped operations by kind (the instruction's stem and the tail
    of its JAX path: ``copy``, ``bitcast_dynamic-update-slice_fusion
    while/body/dynamic_update_slice``), so that what no scope covers
    still has a name."""
    out, unscoped, memo = {}, {}, {}
    for s, e, text in ops:
        if text not in memo:
            name, opcode, _ = trace.parse_instruction(text)
            paths = [p for p in op_names.get(name, ()) if not p.startswith("@")]
            scope = None if opcode in trace.CONTAINERS else \
                (scope_of(paths) or "none")
            tail = "/".join(paths[0].split("/")[-3:]) if paths else ""
            memo[text] = (scope, (name.split(".")[0] + " " + tail).strip())
        scope, kind = memo[text]
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + (e - s)
        if scope == "none":
            unscoped[kind] = unscoped.get(kind, 0.0) + (e - s)
    return {"by_scope": out, "unscoped": unscoped,
            "busy_s": trace._length(trace._union([(s, e) for s, e, _ in ops]))}


class Split:
    """The two reductions of one traced serving run, and what the
    per-layer readers take from them."""

    def __init__(self, threads: dict, ops: list, op_names: dict,
                 window=None):
        self.idle = book_idle(threads, ops, window)
        lo, hi = self.idle["window"]
        spans = [sp for evs in threads.values() for sp in evs]
        inside = [sp for sp in spans if lo <= sp[0] < hi]
        # a step is one launch begun inside the window
        self.steps = len({st.get("sid") for _, _, nm, st in inside
                          if nm in LAUNCH})
        self.hop_us = sum(float(st.get("hop_us", 0.0))
                          for _, _, nm, st in inside
                          if nm in LAUNCH or nm == WAIT)
        self.spans = {}
        for s, e, nm, _ in inside:
            n, tot = self.spans.get(nm, (0, 0.0))
            self.spans[nm] = (n + 1, tot + e - s)
        self.scopes = book_scopes(
            trace.clip(ops, window) if window else ops, op_names)

    def idle_s(self, *names) -> float:
        return sum(self.idle["by_span"].get(n, 0.0) for n in names)

    def idle_ms_per_step(self, *names):
        return 1e3 * self.idle_s(*names) / self.steps if self.steps else None

    def scope_share(self, *scopes):
        """Percent of device-busy time under these scopes; None where no
        operation carries any of the program's scopes."""
        by = self.scopes["by_scope"]
        if not self.scopes["busy_s"] or set(by) <= {"none"}:
            return None
        return 100.0 * sum(by.get(s, 0.0) for s in scopes) \
            / self.scopes["busy_s"]

    def notes(self):
        by = self.idle["by_span"]
        four = self.idle_s(*ENGINE_HOST) + self.idle_s(ROUTE) \
            + self.idle_s(APPLY) + self.idle_s("handoff")
        note("program_spans", steps=self.steps,
             window_s=self.idle["window"][1] - self.idle["window"][0],
             idle_s=self.idle["idle_s"],
             idle_ms_per_step=(1e3 * self.idle["idle_s"] / self.steps
                               if self.steps else None),
             engine_host_s=self.idle_s(*ENGINE_HOST),
             route_s=self.idle_s(ROUTE), apply_s=self.idle_s(APPLY),
             handoff_s=self.idle_s("handoff"),
             unattributed_s=self.idle_s("unattributed"),
             other_spans_s=self.idle["idle_s"] - four
             - self.idle_s("unattributed"),
             guard_hop_ms=self.hop_us / 1e3,
             spans={nm: {"count": n, "total_s": tot,
                         "idle_s": by.get(nm, 0.0)}
                    for nm, (n, tot) in sorted(self.spans.items())})
        sc = self.scopes
        note("device_scopes", busy_s=sc["busy_s"],
             seconds=dict(sorted(sc["by_scope"].items())),
             unscoped_share=(100.0 * sc["by_scope"].get("none", 0.0)
                             / sc["busy_s"] if sc["busy_s"] else None),
             unscoped_top=sorted(sc["unscoped"].items(),
                                 key=lambda kv: -kv[1])[:6])


def of(rec):
    """The ``Split`` of a traced serving run, computed once (and its two
    lines printed once); None where there is no trace, no device
    operation or no ``ds.*`` event.  The window is the one ``trace.reduce``
    cut (``rec["trace"]``, which run.py fills before any reader runs)."""
    if "_program_spans" not in rec:
        split = None
        path = trace.find_xplane(rec["trace_dir"]) \
            if rec.get("kind") == "serve" and rec.get("trace_dir") else None
        if path:
            threads, ops, op_names = read(path)
            if threads and ops:
                split = Split(threads, ops, op_names,
                              (rec.get("trace") or {}).get("window"))
                split.notes()
        rec["_program_spans"] = split
    return rec["_program_spans"]
