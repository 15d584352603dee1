"""Merge a capture window's host spans with its ``jax.profiler`` device
artifact into ONE Perfetto-loadable timeline
(docs/OBSERVABILITY.md "Anomaly detection & deep capture").

The program's spans (telemetry/tracer.py, names ``ds.<layer>.<phase>``)
are mirrored into the profiler session as ``TraceMe`` events, so the
device artifact already holds them on the clock of its ``XLA Ops``
lines: host stages (schedule / stage / dispatch / wait / readback,
each carrying its step ``sid``) and device/XLA activity (including the
``jax.named_scope`` labels) share a clock by construction.  The merge
takes the host spans from there and re-homes them onto host tracks
(pid 1).  To keep every capture on the process's ``perf_counter``
timeline (a fleet merge aligns N windows on it), the whole artifact is
shifted by ONE offset, and that offset is measured, not guessed: the
same span is in the SpanTracer ring (``host_trace.json``,
``perf_counter_ns``) and in the artifact, so the median difference of
the matched spans is the clocks' offset.  ``otherData.clock`` says how
far the capture's anchor (``perf_counter_ns`` when ``start_trace``
returned) was from it.

Only a capture whose artifact holds no ``ds.*`` event (no profiler, or
a host-only window) falls back to ``host_trace.json`` plus that anchor;
``start_trace`` takes tenths of a second to seconds, the capture stamps
both sides of it, and the fallback reports the distance as its
uncertainty.

Device-artifact handling, in preference order:

* ``*.trace.json.gz`` under the capture's ``device/`` dir — already
  Chrome-trace events, session-relative microseconds; shifted by the
  anchor and merged as-is.
* ``*.xplane.pb`` — decoded by the minimal pure-python protobuf reader
  below (XSpace/XPlane/XLine/XEvent; no tensorflow/xprof dependency),
  for jaxlib builds that emit only the xplane.
* neither — the merge still completes, host-only, and says so loudly
  in ``otherData.device_absent`` (the loud-but-absent contract).

CLI::

    python -m tools.tracemerge CAPTURE_DIR [-o merged.json]
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# minimal protobuf wire-format reader (just enough for XSpace)
# --------------------------------------------------------------------------


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """Yield (field_number, wire_type, value) over one message body.
    Length-delimited values come back as bytes; varints as ints;
    fixed32/64 as raw ints."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


def _decode_event_metadata(buf: bytes) -> Tuple[int, str]:
    mid, name = 0, ""
    for fno, _, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 2:
            name = v.decode("utf-8", "replace")
        elif fno == 4 and not name:
            name = v.decode("utf-8", "replace")
    return mid, name


def _decode_xstat(buf: bytes) -> Tuple[int, Any]:
    """(stat metadata id, value) of one XStat: double 2 | uint64 3 |
    int64 4 | str 5."""
    import struct
    mid, val = 0, None
    for fno, wt, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 2 and wt == 1:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif fno == 3:
            val = v
        elif fno == 4:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif fno == 5:
            val = v.decode("utf-8", "replace")
    return mid, val


def _decode_xevent(buf: bytes) -> Dict[str, Any]:
    ev: Dict[str, Any] = {"metadata_id": 0, "offset_ps": 0,
                          "duration_ps": 0}
    for fno, _, v in _fields(buf):
        if fno == 1:
            ev["metadata_id"] = v
        elif fno == 2:
            ev["offset_ps"] = v
        elif fno == 3:
            ev["duration_ps"] = v
        elif fno == 4:
            # XStat: a TraceMe's args (the ds.* spans' sid, counts)
            ev.setdefault("stats", []).append(_decode_xstat(v))
    return ev


def _decode_xline(buf: bytes) -> Dict[str, Any]:
    line = {"id": 0, "name": "", "timestamp_ns": 0, "events": []}
    for fno, _, v in _fields(buf):
        if fno == 1:
            line["id"] = v
        elif fno == 2:
            line["name"] = v.decode("utf-8", "replace")
        elif fno == 11 and not line["name"]:
            line["name"] = v.decode("utf-8", "replace")
        elif fno == 3:
            line["timestamp_ns"] = v
        elif fno == 4:
            line["events"].append(_decode_xevent(v))
    return line


def _decode_xplane(buf: bytes) -> Dict[str, Any]:
    plane = {"id": 0, "name": "", "lines": [], "event_metadata": {},
             "stat_metadata": {}}
    for fno, _, v in _fields(buf):
        if fno == 1:
            plane["id"] = v
        elif fno == 2:
            plane["name"] = v.decode("utf-8", "replace")
        elif fno == 3:
            plane["lines"].append(_decode_xline(v))
        elif fno == 4:
            # map<int64, XEventMetadata> entry: key=1, value=2
            k, meta = None, None
            for efno, _, ev in _fields(v):
                if efno == 1:
                    k = ev
                elif efno == 2:
                    meta = _decode_event_metadata(ev)
            if meta is not None:
                plane["event_metadata"][k if k is not None
                                        else meta[0]] = meta[1]
        elif fno == 5:
            # map<int64, XStatMetadata>: the stats' names
            for efno, _, ev in _fields(v):
                if efno == 2:
                    mid, name = _decode_event_metadata(ev)
                    plane["stat_metadata"][mid] = name
    return plane


def decode_xspace(buf: bytes) -> List[Dict[str, Any]]:
    """Planes of one serialized ``XSpace`` (tensorflow xplane.proto) —
    enough structure for timeline rendering: plane/line names, line
    timestamps, events with metadata-resolved names."""
    return [_decode_xplane(v) for fno, _, v in _fields(buf) if fno == 1]


def xplane_chrome_events(path: str, t_session_epoch_ns: int,
                         pid_base: int = 2000) -> List[Dict[str, Any]]:
    """Chrome trace events (session-relative microsecond ``ts``) from
    one ``*.xplane.pb``.  Line timestamps that look epoch-absolute
    (> ~3 years in ns) are rebased on the capture's epoch anchor;
    small ones are taken as session-relative already."""
    with open(path, "rb") as f:
        planes = decode_xspace(f.read())
    out: List[Dict[str, Any]] = []
    pid = pid_base
    for plane in planes:
        pid += 1
        out.append({"ph": "M", "pid": pid, "tid": 0,
                    "name": "process_name",
                    "args": {"name": plane["name"] or f"plane{pid}"}})
        for line in plane["lines"]:
            tid = int(line["id"]) & 0x7FFFFFFF
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name",
                        "args": {"name": line["name"] or f"line{tid}"}})
            base_ns = line["timestamp_ns"]
            if base_ns > 10**17:          # epoch-absolute ns
                base_ns -= t_session_epoch_ns
            for ev in line["events"]:
                name = plane["event_metadata"].get(
                    ev["metadata_id"], f"event{ev['metadata_id']}")
                rec = {
                    "ph": "X", "pid": pid, "tid": tid,
                    "name": name,
                    "ts": (base_ns + ev["offset_ps"] / 1e3) / 1e3,
                    "dur": ev["duration_ps"] / 1e6,
                }
                if ev.get("stats"):
                    rec["args"] = {
                        plane["stat_metadata"].get(m, f"stat{m}"): v
                        for m, v in ev["stats"] if v is not None}
                out.append(rec)
    return out


# --------------------------------------------------------------------------
# HLO op-name harvesting (named_scope labels)
# --------------------------------------------------------------------------

def _try_str(v: bytes) -> Optional[str]:
    try:
        s = v.decode("utf-8")
    except Exception:  # tpulint: disable=silent-except — utf-8 probe: most length-delimited fields are submessages, not strings
        return None
    return s if s and s.isprintable() else None


def hlo_op_name_map(xplane_path: str) -> Dict[str, Tuple[str, ...]]:
    """instruction name -> every ``metadata.op_name`` seen for it (the
    ``jax.named_scope`` paths, e.g. ``jit(f)/.../t3_mm_ar_comm_t0_ar/
    psum``), harvested from the HLO protos the profiler embeds in the
    xplane's metadata plane.

    The device timeline names events by bare HLO instruction
    (``all-reduce.4``) — the scope labels live only in each
    instruction's OpMetadata.  We walk the nested protobuf generically:
    any submessage whose field 1 is a printable string and whose
    field 7 (OpMetadata) carries a '/'-scoped field-2 string is an
    instruction/name pair.  Bare instruction names COLLIDE across
    modules (every program compiled in the process embeds metadata, and
    ``all-reduce.4`` of one module is unrelated to another's), and the
    timeline events carry no module identity to disambiguate by — so
    ALL distinct op_names per instruction are kept, in walk order, and
    the annotation surfaces every candidate rather than letting
    whichever module was walked first shadow the rest."""
    with open(xplane_path, "rb") as f:
        buf = f.read()
    out: Dict[str, Tuple[str, ...]] = {}

    def walk(b: bytes, depth: int) -> None:
        if depth > 12:
            return
        try:
            fs = list(_fields(b))
        except Exception:  # tpulint: disable=silent-except — wire probe: string payloads misparse as submessages by design
            return
        name = op = None
        for fno, wt, v in fs:
            if wt != 2:
                continue
            s = _try_str(v)
            if s is not None:
                if fno == 1 and name is None:
                    name = s
                continue
            if fno == 7:
                try:
                    for f2, w2, v2 in _fields(v):
                        if f2 == 2 and w2 == 2:
                            s2 = _try_str(v2)
                            if s2 and "/" in s2:
                                op = s2
                except Exception:  # tpulint: disable=silent-except — wire probe: field 7 need not be OpMetadata everywhere
                    pass
            walk(v, depth + 1)
        if name and op:
            have = out.get(name, ())
            if op not in have:
                out[name] = have + (op,)

    # walk ONLY each plane's event_metadata table (field 4) — the HLO
    # protos live there; the event lines (field 3) are the bulk of a
    # real capture's bytes and contain no names worth harvesting
    try:
        for fno, _, plane in _fields(buf):
            if fno != 1:
                continue
            for f2, w2, v2 in _fields(plane):
                if f2 == 4 and w2 == 2:
                    walk(v2, 0)
    except Exception as e:
        # a corrupt/truncated xplane (or a layout change in a new
        # jaxlib) must say so — a silent empty map would later surface
        # as a misleading "no device event carries scope" violation
        print(f"tracemerge: xplane op-name harvest failed on "  # tpulint: disable=print — CLI/loud-degradation output
              f"{xplane_path}: {type(e).__name__}: {e}; merged "
              "timeline will lack scoped op_name annotations")
    return out


def annotate_op_names(events: List[Dict[str, Any]],
                      op_names: Dict[str, Tuple[str, ...]]) -> int:
    """Attach ``args.op_name`` to duration events whose bare
    instruction name is in the map; returns how many were annotated.
    Cross-module name collisions surface EVERY candidate (joined with
    `` | ``) — the window genuinely executed an instruction of that
    name, and hiding all but one module's scope made the timeline (and
    ``validate_merged_trace``'s scope check) depend on protobuf walk
    order."""
    n = 0
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        scoped = op_names.get(ev.get("name", ""))
        if scoped:
            args = ev.setdefault("args", {})
            if isinstance(args, dict):
                args["op_name"] = " | ".join(scoped)
                n += 1
    return n


# --------------------------------------------------------------------------
# device-artifact loading
# --------------------------------------------------------------------------

def load_device_events(device_dir: str,
                       t_session_epoch_ns: int) -> List[Dict[str, Any]]:
    """Chrome events (session-relative µs) from a jax profiler log dir:
    prefers the ``trace.json.gz`` the profiler already renders, falls
    back to decoding ``xplane.pb`` directly.  Either way, events whose
    instruction appears in the xplane's HLO metadata gain an
    ``args.op_name`` with the full ``jax.named_scope`` path — the T3
    tile-comm scopes are only visible through it where the timeline
    names events by bare instruction."""
    pbs = sorted(glob.glob(os.path.join(device_dir, "**", "*.xplane.pb"),
                           recursive=True))
    gz = sorted(glob.glob(os.path.join(device_dir, "**",
                                       "*.trace.json.gz"),
                          recursive=True))
    events: List[Dict[str, Any]] = []
    if gz:
        with gzip.open(gz[-1], "rt") as f:
            events = json.load(f).get("traceEvents", [])
    elif pbs:
        events = xplane_chrome_events(pbs[-1], t_session_epoch_ns)
    if events and pbs:
        # annotate is keyed on the bare instruction name, so it leaves
        # alone any event a backend already names by its scoped path.
        # (Guessing "already scoped" from a "/" in some event name
        # skipped the harvest altogether once XLA:CPU's thread pool
        # began emitting "Wait: pending_threads=1/2" events.)
        annotate_op_names(events, hlo_op_name_map(pbs[-1]))
    return events


# --------------------------------------------------------------------------
# merge
# --------------------------------------------------------------------------

PROGRAM_SPAN_PREFIX = "ds."


def _xplane_program_spans(path: str) -> List[Dict[str, Any]]:
    """Every ``ds.*`` event on the ``/host:`` planes of one
    ``*.xplane.pb``: ``{"name", "thread", "thread_name", "ts", "dur"
    (µs, the artifact's clock), "args"}``.  Read from the xplane, not
    from the ``trace.json.gz`` beside it: the profiler caps that
    rendering at a million events, and a window of seconds loses its
    later steps there.  ``jax.profiler.ProfileData`` reads it where JAX
    is installed (a second for two million events); the pure-python
    decoder above is the fallback."""
    out: List[Dict[str, Any]] = []
    try:
        from jax.profiler import ProfileData
    except ImportError:
        ProfileData = None
    if ProfileData is not None:
        for pi, plane in enumerate(ProfileData.from_file(path).planes):
            if not plane.name.startswith("/host:"):
                continue
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN_PREFIX):
                        out.append({
                            "name": ev.name, "thread": f"{pi}.{li}",
                            "thread_name": line.name,
                            "ts": ev.start_ns / 1e3,
                            "dur": ev.duration_ns / 1e3,
                            "args": dict(ev.stats)})
        return out
    with open(path, "rb") as f:
        planes = decode_xspace(f.read())
    for pi, plane in enumerate(planes):
        if not plane["name"].startswith("/host:"):
            continue
        for li, line in enumerate(plane["lines"]):
            for ev in line["events"]:
                name = plane["event_metadata"].get(ev["metadata_id"], "")
                if name.startswith(PROGRAM_SPAN_PREFIX):
                    out.append({
                        "name": name, "thread": f"{pi}.{li}",
                        "thread_name": line["name"],
                        "ts": (line["timestamp_ns"]
                               + ev["offset_ps"] / 1e3) / 1e3,
                        "dur": ev["duration_ps"] / 1e6,
                        "args": {
                            plane["stat_metadata"].get(m, f"stat{m}"): v
                            for m, v in ev.get("stats", ())
                            if v is not None}})
    return out


def program_span_events(xplane_path: str) -> List[Dict[str, Any]]:
    """The program's ``ds.*`` spans of one device artifact as host
    tracks of a Chrome trace — pid 1, one tid per source thread — on
    the artifact's own clock.  ``[]`` when the artifact holds none."""
    spans = sorted(_xplane_program_spans(xplane_path),
                   key=lambda sp: sp["ts"])
    if not spans:
        return []
    host: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
        "args": {"name": "deepspeed_tpu (spans in the device trace)"}}]
    tids: Dict[str, int] = {}
    body: List[Dict[str, Any]] = []
    for sp in spans:
        tid = tids.get(sp["thread"])
        if tid is None:
            tid = tids[sp["thread"]] = len(tids) + 1
            host.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {
                             "name": f"{sp['thread_name'] or 'thread'} "
                                     f"#{tid}"}})
        rec = {"name": sp["name"], "ph": "X", "pid": 1, "tid": tid,
               "ts": sp["ts"], "dur": sp["dur"]}
        if sp["args"]:
            rec["args"] = sp["args"]
        body.append(rec)
    return host + body


def measured_offset_us(ring_events: List[Dict[str, Any]],
                       program_events: List[Dict[str, Any]]
                       ) -> Optional[float]:
    """perf_counter minus the artifact's clock, in µs: the median over
    the spans found in both the SpanTracer ring and the artifact,
    matched by name and step id (``sid``/``step``).  None without a
    match."""
    def key(ev):
        args = ev.get("args") if isinstance(ev.get("args"), dict) else {}
        sid = args.get("sid", args.get("step"))
        return None if sid is None else (ev.get("name"), sid)

    ring = {}
    for ev in ring_events:
        if ev.get("ph") == "X" and key(ev) is not None:
            ring.setdefault(key(ev), ev["ts"])
    diffs = sorted(ring[key(ev)] - ev["ts"] for ev in program_events
                   if ev.get("ph") == "X" and key(ev) in ring)
    return diffs[len(diffs) // 2] if diffs else None


def merge_events(host_events: List[Dict[str, Any]],
                 device_events: List[Dict[str, Any]],
                 t_start_perf_ns: int) -> List[Dict[str, Any]]:
    """Put both event streams on the host ``perf_counter`` timeline
    (microseconds): host events already are; device events are on the
    artifact's clock and get shifted by ``t_start_perf_ns`` (the
    measured offset, or the capture's anchor).  Device pids are bumped
    out of the host's pid space so Perfetto renders host stages and
    device activity as separate process groups."""
    anchor_us = t_start_perf_ns / 1e3
    out: List[Dict[str, Any]] = list(host_events)
    for ev in device_events:
        if not isinstance(ev, dict) or "ph" not in ev:
            continue      # the profiler emits a trailing partial record
        ev = dict(ev)
        pid = ev.get("pid", 0)
        ev["pid"] = pid + 10_000 if pid < 10_000 else pid
        if ev.get("ph") in ("X", "i", "b", "e") and "ts" in ev:
            ev["ts"] = ev["ts"] + anchor_us
        out.append(ev)
    return out


def _capture_events(capture_dir: str
                    ) -> Tuple[List[Dict[str, Any]], Dict[str, Any],
                               int, bool]:
    """One capture window's events on the host ``perf_counter``
    timeline (µs).  Host spans come from the device artifact when the
    program's ``ds.*`` events are in it (one clock by construction,
    shifted as a whole by the measured offset); else from
    ``host_trace.json``, with the device events shifted by the
    capture's anchor.  ``meta["clock"]`` says which, and how far the
    anchor was off or may be off.  Returns ``(events, meta,
    n_host_events, device_absent)`` — the shared core of
    :func:`merge_capture` and :func:`merge_fleet` (a fleet merge aligns
    N windows from N replicas on one timeline)."""
    with open(os.path.join(capture_dir, "meta.json")) as f:
        meta = json.load(f)
    host: Dict[str, Any] = {"traceEvents": []}
    if meta.get("host_trace"):
        with open(os.path.join(capture_dir, meta["host_trace"])) as f:
            host = json.load(f)
    device_events: List[Dict[str, Any]] = []
    program: List[Dict[str, Any]] = []
    device_absent = True
    if meta.get("device_dir"):
        ddir = os.path.join(capture_dir, meta["device_dir"])
        if os.path.isdir(ddir):
            device_events = load_device_events(
                ddir, meta.get("t_start_epoch_ns", 0))
            device_absent = not device_events
            pbs = sorted(glob.glob(os.path.join(ddir, "**", "*.xplane.pb"),
                                   recursive=True))
            if pbs:
                program = program_span_events(pbs[-1])
    ring_events = host.get("traceEvents", [])
    anchor_us = meta["t_start_perf_ns"] / 1e3
    if program:
        offset_us = measured_offset_us(ring_events, program)
        clock = {"host_spans_from": "device_artifact",
                 "offset_measured": offset_us is not None}
        if offset_us is None:
            offset_us = anchor_us
        else:
            clock["anchor_error_us"] = anchor_us - offset_us
        host_events = [dict(ev, ts=ev["ts"] + offset_us)
                       if "ts" in ev else ev for ev in program]
        # re-homed above: not a second time among the device's threads
        device_events = [
            ev for ev in device_events
            if not (isinstance(ev, dict) and str(ev.get("name", ""))
                    .startswith(PROGRAM_SPAN_PREFIX))]
    else:
        offset_us = anchor_us
        host_events = ring_events
        clock = {"host_spans_from": "host_trace",
                 "offset_measured": False}
        if "t_before_start_perf_ns" in meta:
            clock["anchor_uncertainty_us"] = (
                meta["t_start_perf_ns"]
                - meta["t_before_start_perf_ns"]) / 1e3
    clock["offset_us"] = offset_us
    meta = {**meta, "clock": clock}
    return (merge_events(host_events, device_events,
                         int(offset_us * 1e3)),
            meta, len(host_events), device_absent)


def merge_capture(capture_dir: str,
                  out_path: Optional[str] = None) -> str:
    """Merge one capture window's artifacts
    (telemetry/profiler.py layout: ``meta.json`` + ``host_trace.json``
    + ``device/``) into a single Perfetto-loadable Chrome trace;
    returns the written path (default ``<capture_dir>/merged.json``)."""
    events, meta, n_host, device_absent = _capture_events(capture_dir)
    if device_absent:
        print(f"tracemerge: NO device events under {capture_dir} — "  # tpulint: disable=print — CLI/loud-degradation output
              "emitting a host-only timeline (profiler absent or "
              "unsupported on this backend/build)")
    merged = {
        "displayTimeUnit": "ms",
        "traceEvents": events,
        "otherData": {
            "merged_by": "tools/tracemerge",
            "capture": meta,
            "clock": meta["clock"],
            "host_events": n_host,
            "device_events": len(events) - n_host,
            "device_absent": device_absent,
        },
    }
    out_path = out_path or os.path.join(capture_dir, "merged.json")
    with open(out_path, "w") as f:
        json.dump(merged, f)
    return out_path


# --------------------------------------------------------------------------
# fleet merge: router trace + N replica capture artifacts
# --------------------------------------------------------------------------

# per-replica pid stride in a --fleet merge: replica i's events (host
# AND device — the capture's own +10000 device bump rides inside) are
# shifted by (i+1) * stride, so each replica renders as its own
# Perfetto process group while the router trace keeps the base pids
_FLEET_PID_STRIDE = 100_000


def merge_fleet(fleet_dir: str, out_path: Optional[str] = None) -> str:
    """Merge a fleet post-mortem bundle (``FleetRouter.debug_dump``
    layout: ``fleet.json`` + ``router_trace.json`` + per-replica
    capture artifacts) onto ONE Perfetto timeline
    (docs/OBSERVABILITY.md "Fleet observability").

    The router's span ring — placement / migrate / failover spans and
    journey instants, each carrying ``uid`` + ``replica`` args — stays
    at the base pids; every replica's capture windows merge through
    their OWN clock anchors (all replicas share the in-process
    ``perf_counter`` clock) and are shifted into a per-replica pid
    range, so one request's journey is flow-connectable across the
    router track and the replica process groups by its shared ``uid``
    arg.  Replicas whose captures are missing are reported loudly and
    skipped — the merge still completes."""
    with open(os.path.join(fleet_dir, "fleet.json")) as f:
        dump = json.load(f)
    events: List[Dict[str, Any]] = []
    if dump.get("router_trace"):
        with open(os.path.join(fleet_dir, dump["router_trace"])) as f:
            events.extend(json.load(f).get("traceEvents", []))
    else:
        print(f"tracemerge: fleet bundle {fleet_dir} carries no "  # tpulint: disable=print — CLI/loud-degradation output
              "router trace (telemetry plane off?) — replica tracks "
              "only")
    per_replica: Dict[str, int] = {}
    device_absent = True
    for i, name in enumerate(sorted(dump.get("replicas", {}))):
        info = dump["replicas"][name]
        offset = (i + 1) * _FLEET_PID_STRIDE
        n_ev = 0
        for cdir in info.get("captures", ()):
            if not os.path.isdir(cdir):
                rel = os.path.join(fleet_dir, cdir)
                if os.path.isdir(rel):
                    cdir = rel
                else:
                    print(f"tracemerge: replica {name} capture "  # tpulint: disable=print — CLI/loud-degradation output
                          f"{cdir} missing — skipped")
                    continue
            try:
                evs, _, n_host, absent = _capture_events(cdir)
            except (OSError, ValueError, KeyError) as e:
                print(f"tracemerge: replica {name} capture {cdir} "  # tpulint: disable=print — CLI/loud-degradation output
                      f"unreadable ({type(e).__name__}: {e}) — skipped")
                continue
            device_absent = device_absent and absent
            for ev in evs:
                if not isinstance(ev, dict):
                    continue
                ev = dict(ev)
                ev["pid"] = ev.get("pid", 0) + offset
                if ev.get("name") == "process_name" \
                        and isinstance(ev.get("args"), dict):
                    ev["args"] = {**ev["args"],
                                  "name": f"replica {name}: "
                                          f"{ev['args'].get('name', '')}"}
                events.append(ev)
                n_ev += 1
        per_replica[name] = n_ev
    merged = {
        "displayTimeUnit": "ms",
        "traceEvents": events,
        "otherData": {
            "merged_by": "tools/tracemerge --fleet",
            "fleet": {"reason": dump.get("reason"),
                      "steps": dump.get("steps")},
            "replica_events": per_replica,
            "replica_groups": sum(1 for n in per_replica.values() if n),
            "device_absent": device_absent,
        },
    }
    out_path = out_path or os.path.join(fleet_dir, "merged_fleet.json")
    with open(out_path, "w") as f:
        json.dump(merged, f)
    return out_path


def validate_merged_trace(obj: Dict[str, Any],
                          require_device: bool = True,
                          require_scopes: Sequence[str] = (),
                          require_replicas: int = 0) -> List[str]:
    """Schema check for a merged timeline: returns violations (empty
    when valid).  Valid means Chrome-trace-shaped (``traceEvents`` list
    of dicts with ``ph``), containing at least one host SpanTracer
    track (pid 1 thread_name metadata) and — unless ``require_device``
    is off — at least one device-derived duration event (pid whose
    in-group offset is >= 10000; in a ``--fleet`` merge each replica's
    events live in their own pid group of stride 100000, the device
    bump riding inside).  ``require_scopes``: substrings that must
    each match some device event's name or scoped ``args.op_name`` —
    how a test pins the T3 tile-comm scopes to actual device activity.
    ``require_replicas``: minimum number of distinct replica process
    groups a ``--fleet`` merge must carry (the multi-replica presence
    bar — a fleet timeline with one replica track explains nothing
    about the fleet)."""
    problems: List[str] = []
    evs = obj.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    if not all(isinstance(e, dict) and "ph" in e for e in evs):
        problems.append("malformed trace events (dict with 'ph' "
                        "required)")
        return problems
    host_tracks = {e["args"]["name"] for e in evs
                   if e.get("ph") == "M" and e.get("pid") == 1
                   and e.get("name") == "thread_name"
                   and isinstance(e.get("args"), dict)
                   and "name" in e["args"]}
    if not host_tracks:
        problems.append("no host SpanTracer tracks (pid 1 thread_name)")
    host_spans = [e for e in evs if e.get("pid") == 1
                  and e.get("ph") == "X"]
    if not host_spans:
        problems.append("no host span events")
    dev = [e for e in evs
           if e.get("pid", 0) % _FLEET_PID_STRIDE >= 10_000
           and e.get("ph") == "X"]
    if require_device and not dev:
        problems.append("no device-derived events (pid >= 10000)")
    if require_replicas:
        groups = {e.get("pid", 0) // _FLEET_PID_STRIDE for e in evs
                  if e.get("pid", 0) >= _FLEET_PID_STRIDE}
        if len(groups) < require_replicas:
            problems.append(
                f"{len(groups)} replica process group(s) < required "
                f"{require_replicas} (pid stride {_FLEET_PID_STRIDE})")
    for scope in require_scopes:
        if not any(scope in e.get("name", "")
                   or (isinstance(e.get("args"), dict)
                       and scope in e["args"].get("op_name", ""))
                   for e in dev):
            problems.append(
                f"no device event carries scope {scope!r} (name or "
                "args.op_name)")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("capture_dir",
                    help="capture window directory "
                    "(telemetry/profiler.py layout), or with --fleet "
                    "a fleet post-mortem bundle "
                    "(FleetRouter.debug_dump layout)")
    ap.add_argument("-o", "--out", default=None,
                    help="output path (default: "
                    "<capture_dir>/merged.json, or "
                    "<bundle>/merged_fleet.json with --fleet)")
    ap.add_argument("--fleet", action="store_true",
                    help="merge a fleet bundle: router trace + every "
                    "replica's capture artifacts as per-replica "
                    "process groups")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check the merged file and exit "
                    "nonzero on violations (with --fleet, also "
                    "requires >= 2 replica process groups)")
    args = ap.parse_args(argv)
    if args.fleet:
        path = merge_fleet(args.capture_dir, args.out)
    else:
        path = merge_capture(args.capture_dir, args.out)
    print(path)  # tpulint: disable=print — the CLI's one output line
    if args.validate:
        with open(path) as f:
            problems = validate_merged_trace(
                json.load(f),
                require_replicas=2 if args.fleet else 0)
        if problems:
            print("\n".join(problems))  # tpulint: disable=print — CLI output
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
