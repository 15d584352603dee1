"""The reduction from a profiler trace (``.xplane.pb``) to numbers: device
busy union, idle share, time per group of operations, exposed collective
time, and the longest idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` (nothing but JAX).  Kept with the
benchmark so that every PR computes the same number the same way.
tools/tracemerge.py reads the same file for Perfetto and has none of
this arithmetic.

Device planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds
one event per executed HLO operation, named by the instruction's text
(``%fusion.12 = bf16[..] fusion(..), kind=kOutput, calls=..``); a
``while`` is an event too and holds its body's events, so containers
count towards the busy union and not towards any group's sum.
Host spans come from ``jax.profiler.TraceAnnotation`` in the benchmark's
own files; their names start with ``bench.``.

The traced window is cut on the trace's own clock: the drivers hold one
host span, ``bench.trace.window``, open from the line after
``start_trace`` returns to the line before ``stop_trace`` is called
(``common.start_trace`` / ``stop_trace``), and every operation is clipped
to it before anything is summed.  So ``0 < busy_s <= window_s`` holds by
construction, also for a device that never idles, whatever the device
tracer recorded while the profiler itself was starting or stopping.
"""

import glob
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.trace.window"


def hlo_collectives(compiled_text: str) -> dict:
    """Collective operations in a compiled program's HLO text (the idiom
    of chip_smoke.collectives_in), and their total."""
    out = {op: compiled_text.count(f" {op}(") + compiled_text.count(
        f" {op}-start(") for op in COLLECTIVES}
    out["total"] = sum(out.values())
    return out


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


CONTAINERS = ("while", "conditional", "call")     # their children are events too
INSTR = re.compile(r"^%?([^\s=]+)\s*=")
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
KERNELS = (("paged", "paged_attention"), ("flash", "flash_attention"),
           ("mixed_matmul", "mixed_gemm"), ("mixed_gemm", "mixed_gemm"))


def parse_instruction(text: str):
    """(instruction name, opcode, fusion kind) of an event named by its
    HLO text ``%name = type opcode(operands), kind=..., ...``; a bare
    name gives (name, its stem, None).  Operands are never looked at:
    they carry other instructions' names."""
    m = INSTR.match(text)
    if not m:
        name = text.lstrip("%")
        return name, re.split(r"[.\s(]", name)[0], None
    op = OPCODE.search(text, m.end())
    kind = re.search(r"\bkind=k(\w+)", text)
    return m.group(1), (op.group(1) if op else
                        re.split(r"[.\s(]", m.group(1))[0]), \
        (kind.group(1) if kind else None)


def op_group(text: str, op_names: dict = None, aliases: dict = None) -> str:
    """A stable group for one device operation.  ``op_names`` maps an
    instruction's name to the JAX source paths of its HLO metadata
    (``hlo_op_names``); with it a Pallas custom call is named for the
    kernel it is (PR 22 printed ``kernel:closed_call``: a custom call is
    named after the jaxpr it closes over, and only its metadata says
    ``pallas_call``), and a fusion counts as a matrix multiplication
    when its root is a ``dot_general``.  Without it, by what the text
    itself says.  ``aliases`` (a configuration's ``trace_groups``) names
    a kernel whose path does not: a key ``<tail>@<file>`` takes the
    instructions whose path ends in ``tail`` and whose innermost source
    frame lies in a file ending in ``file``, so a second Pallas call made
    from another file is not counted with it."""
    name, opcode, kind = parse_instruction(text)
    seen = (op_names or {}).get(name, ())
    paths = [p for p in seen if not p.startswith("@")]
    files = [p[1:] for p in seen if p.startswith("@")]
    for key, group in (aliases or {}).items():
        tail, _, file = key.partition("@")
        if any(p.endswith(tail) for p in paths) \
                and any(f.endswith(file) for f in files):
            return group
    scopes = " ".join(paths).lower()
    base = opcode[:-6] if opcode.endswith("-start") else \
        opcode[:-5] if opcode.endswith("-done") else opcode
    if base == "async":          # %slice-done.3 = .. async-done(..)
        base = re.split(r"[.]", name)[0]
        for tail in ("-start", "-done", "-update"):
            base = base[:-len(tail)] if base.endswith(tail) else base
    if base in COLLECTIVES:
        return "collectives"
    if base in CONTAINERS:
        return "container"
    if base == "custom-call":
        where = scopes + " " + name.lower()
        for key, group in KERNELS:
            if key in where:
                return group
        return "custom_call:" + re.split(r"[.]", name)[0]
    if base == "fusion":
        if scopes:
            if "dot_general" in scopes or "conv_general" in scopes:
                return "matmul_fusions"
            return "other_fusions"
        stem = name.lower()
        if "convolution" in stem or (kind == "Output"
                                     and re.match(r"fusion(\.|$)", stem)):
            return "matmul_fusions"
        return "other_fusions"
    if base in ("convolution", "dot"):
        return "matmul_fusions"
    return base


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            v, i = buf[i:i + ln], i + ln
            if i > n:
                raise ValueError("truncated")
        else:
            raise ValueError("wire type")
        yield fno, wt, v


def _text(v: bytes):
    try:
        s = v.decode("utf-8")
    except UnicodeDecodeError:
        return None
    return s if s and s.isprintable() else None


def _stack_files(buf: bytes) -> dict:
    """frame id -> source file of that frame, from a StackFrameIndexProto
    (1 file_names, 3 file_locations {1 file_name_id}, 4 stack_frames
    {1 file_location_id}; ids count from 1).  {} if ``buf`` is not one."""
    files, locs, frames = [], [], []
    try:
        for fno, wt, v in _fields(buf):
            if wt != 2:
                return {}
            if fno == 1:
                files.append(_text(v))
            elif fno in (3, 4):
                ids = {a: c for a, b, c in _fields(v) if b == 0}
                (locs if fno == 3 else frames).append(ids.get(1, 0))
        return {i: files[locs[loc - 1] - 1]
                for i, loc in enumerate(frames, 1) if loc and locs[loc - 1]}
    except (ValueError, IndexError):
        return {}


def hlo_op_names(path: str) -> dict:
    """instruction name -> the ``metadata.op_name`` paths seen for it, and
    as ``@<file>`` the source file of its innermost stack frame, from the
    HLO protos the profiler embeds in each plane's event-metadata table.
    (The walk is copied from tools/tracemerge.py ``hlo_op_name_map``: any
    sub-message whose field 1 is a printable string and whose field 7,
    OpMetadata, carries a '/'-scoped field-2 string is an instruction.
    OpMetadata's field 15 is the id of a frame in the module's field 17,
    its stack-frame index.)"""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}

    def walk(b, depth, frames):
        if depth > 12:
            return
        try:
            fs = list(_fields(b))
        except (ValueError, IndexError):
            return                 # a string that is not a message
        for fno, wt, v in fs:
            if fno == 17 and wt == 2:
                frames = _stack_files(v) or frames
        name = op = src = None
        for fno, wt, v in fs:
            if wt != 2:
                continue
            s = _text(v)
            if s is not None:
                if fno == 1 and name is None:
                    name = s
                continue
            if fno == 7:
                try:
                    for f2, w2, v2 in _fields(v):
                        if f2 == 2 and w2 == 2:
                            s2 = _text(v2)
                            if s2 and "/" in s2:
                                op = s2
                        elif f2 == 15 and w2 == 0:
                            src = frames.get(v2)
                except (ValueError, IndexError):
                    pass
            walk(v, depth + 1, frames)
        if name and op:
            for entry in (op, "@" + src if src else None):
                if entry and entry not in out.get(name, ()):
                    out[name] = out.get(name, ()) + (entry,)

    for fno, _, plane in _fields(buf):
        if fno == 1:
            for f2, w2, v2 in _fields(plane):
                if f2 == 4 and w2 == 2:
                    walk(v2, 0, {})
    return out


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged):
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Total length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def read(path: str) -> dict:
    """{"devices": {n: [(start_s, end_s, name)]},
        "host": [(start_s, end_s, name)]}  (seconds on the trace's clock)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                ev.name))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9,
                                     ev.name))
    return {"devices": devices, "host": host, "op_names": hlo_op_names(path)}


def window_of(trace: dict):
    """(start_s, end_s) of the drivers' ``bench.trace.window`` span on the
    trace's clock, or None for a file that holds none (a recorded fixture,
    a capture older than the span)."""
    spans = [(s, e) for s, e, nm in trace.get("host", ()) if nm == WINDOW_SPAN]
    return max(spans, key=lambda w: w[1] - w[0]) if spans else None


def clip(ops: list, window) -> list:
    """The operations that touch ``window``, each cut at its edges."""
    lo, hi = window
    return [(max(s, lo), min(e, hi), nm) for s, e, nm in ops
            if e > lo and s < hi]


def reduce(trace: dict, window=None, top: int = 10,
           aliases: dict = None) -> dict:
    """All the numbers the per-layer readers take from a trace.

    window          (start_s, end_s) on the trace's clock: given, else the
                    ``bench.trace.window`` span, else first op to last op.
                    Everything below counts only what lies inside it; an
                    operation across an edge is cut there
    busy_s          union of op intervals, averaged over the devices
    window_s        the window's length
    idle_share      1 - busy / window (never clamped: below 0 is a fault)
    groups_s        seconds per op group, averaged over the devices
    exposed_collective_s   on device 0: time inside collective ops during
                    which no other op runs there
    idle_gaps       [(host span name, seconds)], the longest idle gaps of
                    device 0 summed by the innermost bench.* span that
                    covers each gap's middle
    """
    devs = trace["devices"]
    if not devs or not any(devs.values()):
        return None
    window = window or window_of(trace)
    cut_by = "span" if window else "ops"
    first_op = min(op[0] for ops in devs.values() for op in ops)
    last_op = max(op[1] for ops in devs.values() for op in ops)
    lo, hi = window or (first_op, last_op)
    devs = {d: clip(ops, (lo, hi)) for d, ops in devs.items()}
    names = trace.get("op_names") or {}
    memo = {}

    def group_of(text):
        if text not in memo:
            memo[text] = op_group(text, names, aliases)
        return memo[text]
    n = len(devs)
    busy = 0.0
    groups = {}
    for ops in devs.values():
        busy += _length(_union([(s, e) for s, e, _ in ops]))
        for s, e, name in ops:
            g = group_of(name)
            if g != "container":
                groups[g] = groups.get(g, 0.0) + (e - s)
    busy /= n
    groups = {g: v / n for g, v in groups.items()}
    first = devs[min(devs)]
    coll = _union([(s, e) for s, e, nm in first
                   if group_of(nm) == "collectives"])
    rest = _union([(s, e) for s, e, nm in first
                   if group_of(nm) not in ("collectives", "container")])
    merged = _union([(s, e) for s, e, _ in first])
    gaps = {}
    spans = sorted((h for h in trace["host"] if h[2] != WINDOW_SPAN),
                   key=lambda h: h[1] - h[0])
    edges = [(lo, lo)] + merged + [(hi, hi)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        mid = (e0 + s1) / 2
        name = next((nm for s, e, nm in spans if s <= mid <= e),
                    "bench.unattributed")
        gaps[name] = gaps.get(name, 0.0) + gap
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "window_s": hi - lo, "window": (lo, hi),
            "cut_by": cut_by, "first_op_s": first_op, "last_op_s": last_op,
            "idle_share": 1.0 - busy / (hi - lo) if hi > lo else None,
            "groups_s": groups,
            "exposed_collective_s": _subtract(coll, rest),
            "collective_s": _length(coll),
            "device_ops": [[k, v] for k, v in rank(groups)],
            "idle_gaps": [[k, v] for k, v in rank(gaps)],
            "devices": n, "op_events": sum(len(o) for o in devs.values())}


def reduce_dir(trace_dir: str, aliases: dict = None):
    path = find_xplane(trace_dir) if trace_dir else None
    if not path:
        return None
    return reduce(read(path), aliases=aliases)
