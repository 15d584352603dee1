"""What a train step says about its own parts inside a profiler trace:
device time by the ``jax.named_scope`` names of the training forward
(``models/transformer.py``), the loss, the step (``runtime/engine.py``)
and ZeRO-3's gathers (``parallel/zero.py``), and by pass.

Autodiff and ``jax.checkpoint`` write the pass into an operation's JAX
path; these three markers are the contract between program and reader
(``tests/test_train_scopes.py`` holds the program to them):

    jit(train_step)/jvp(layer_scan)/while/body/closed_call/qkv/dot_general   forward
    jit(train_step)/jvp(loss)/reduce_sum                                     forward
    .../transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/rematted_computation/ffn/tanh   recompute
    .../transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/ffn/dot_general   backward
    jit(train_step)/optimizer/sub                                            update

A scope inside the layer scan is a whole path component; one directly
under a transform is wrapped by it (``jvp(loss)``,
``transpose(jvp(unembed))``) and is unwrapped here.

One reduction of device 0 of the traced window (``trace.reduce``'s, to
which every operation is clipped; containers hold their bodies' events
and are left out): every operation's seconds go to

* a **scope**: the first of ``SCOPES`` that one of its JAX paths holds as
  a component, else ``none``.  ``zero_gather`` and ``zero_scatter`` come
  first, so a gather made inside ``embed`` is a gather; ``layer_scan``
  (the whole scan over the layers) comes last, so it keeps only what the
  scan does outside its body: a layer's weights cut out of the stack,
  the saved activations and the weight gradients stacked and cut again;
* a **pass**: ``update`` under one of the step's own scopes (``UPDATE``),
  else ``recompute`` where the path holds ``rematted_computation``, else
  ``backward`` where it holds ``transpose(``, else ``forward``;
* its ``trace.op_group``, the names of the ledger's ``breakdown``.

A step is one ``ds.train.dispatch`` span of the program begun inside the
window (its ``step`` stat), not a stamp of the driver's.

What this cannot see: a fusion is booked whole to the scope of its root;
a collective the compiler spreads over fusions is booked to them; only
device 0 is read.  A trace in which no operation carries a training
scope (the parent of the PR that added them, a serving cell, a rehearsal
on the CPU) gives ``None``: the readers then report nothing.
"""

import re
import time

from benchmarks.lib import program_spans, trace
from benchmarks.lib.common import note

SCOPES = ("zero_gather", "zero_scatter", "cast_params", "grad_accumulate",
          "grad_epilogue", "optimizer", "embed", "qkv", "attn_out", "attn",
          "ffn", "unembed", "loss", "layer_scan")
UPDATE = frozenset(("cast_params", "grad_accumulate", "grad_epilogue",
                    "optimizer"))
PASSES = ("forward", "recompute", "backward", "update")
REMAT = "rematted_computation"
DISPATCH = "ds.train.dispatch"
WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def components(path: str) -> list:
    """The components of a JAX path, each with the transforms around it
    taken off: ``transpose(jvp(loss))`` is ``loss``."""
    out = []
    for part in path.split("/"):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        out.append(part)
    return out


def pass_of(path: str, parts=None) -> str:
    parts = components(path) if parts is None else parts
    if not UPDATE.isdisjoint(parts):
        return "update"
    if REMAT in parts:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


def classify(paths) -> tuple:
    """(scope, pass) of an operation from its JAX paths: those of the
    first path that holds a training scope, else ``none`` and the first
    path's pass (``forward`` for an operation with no path at all)."""
    paths = [p for p in paths if not p.startswith("@")]
    for p in paths:
        parts = components(p)
        for scope in SCOPES:
            if scope in parts:
                return scope, pass_of(p, parts)
    return "none", (pass_of(paths[0]) if paths else "forward")


def collective_kind(text: str):
    """``all-gather`` etc. for a collective's event (its ``-start`` and
    ``-done`` halves too), and whether the event begins one."""
    name, opcode, _ = trace.parse_instruction(text)
    kind = next((k for k in trace.COLLECTIVES
                 if opcode.startswith(k) or name.startswith(k)), "collective")
    return kind, not (opcode.endswith("-done")
                      or name.split(".")[0].endswith("-done"))


def book(ops: list, op_names: dict, aliases: dict = None) -> dict:
    """Device 0's operation seconds by scope, by pass, by scope and pass,
    by scope and group, containers left out; the collectives' events and
    seconds by scope, pass and kind; the unscoped operations by kind as
    ``program_spans.book_scopes`` names them; the busy union."""
    by_scope, by_pass, by_sp, by_sg = {}, {}, {}, {}
    coll, unscoped, memo = {}, {}, {}
    total = 0.0

    def add(d, k, v):
        d[k] = d.get(k, 0.0) + v

    for s, e, text in ops:
        if text not in memo:
            name, opcode, _ = trace.parse_instruction(text)
            if opcode in trace.CONTAINERS:
                memo[text] = None
            else:
                paths = [p for p in op_names.get(name, ())
                         if not p.startswith("@")]
                tail = "/".join(paths[0].split("/")[-3:]) if paths else ""
                group = trace.op_group(text, op_names, aliases)
                memo[text] = classify(paths) + (
                    group, (name.split(".")[0] + " " + tail).strip(),
                    collective_kind(text) if group == "collectives" else None)
        if memo[text] is None:
            continue
        scope, pss, group, kind, collective = memo[text]
        d = e - s
        total += d
        add(by_scope, scope, d)
        add(by_pass, pss, d)
        add(by_sp, f"{scope}|{pss}", d)
        add(by_sg, f"{scope}|{group}", d)
        if scope == "none":
            add(unscoped, kind, d)
        if collective:
            ckind, begins = collective
            c = coll.setdefault(f"{scope}|{pss}|{ckind}",
                                {"events": 0, "seconds": 0.0})
            c["events"] += int(begins)
            c["seconds"] += d
    return {"op_s": total, "by_scope": by_scope, "by_pass": by_pass,
            "by_scope_pass": by_sp, "by_scope_group": by_sg,
            "collectives": coll, "unscoped": unscoped,
            "busy_s": trace._length(trace._union([(s, e) for s, e, _ in ops]))}


class Booked:
    """The reduction of one traced training run, and what the per-layer
    readers take from it."""

    def __init__(self, threads: dict, ops: list, op_names: dict,
                 window=None, aliases: dict = None):
        if window:
            ops = trace.clip(ops, window)
        self.idle = program_spans.book_idle(threads, ops, window)
        lo, hi = self.idle["window"]
        self.steps = len({st.get("step") for evs in threads.values()
                          for s, _, nm, st in evs
                          if nm == DISPATCH and lo <= s < hi})
        self.booked = book(ops, op_names, aliases)

    @property
    def scoped(self) -> bool:
        return not set(self.booked["by_scope"]) <= {"none"}

    def share(self, by: str, *keys):
        """Percent of device-busy time under these keys of ``by_scope``
        or ``by_pass``."""
        busy = self.booked["busy_s"]
        if not busy:
            return None
        return 100.0 * sum(self.booked[by].get(k, 0.0) for k in keys) / busy

    def device_step_ms(self):
        return 1e3 * self.booked["busy_s"] / self.steps if self.steps \
            else None

    def passes_under(self, scope: str):
        """In how many of forward, recompute, backward an operation under
        ``scope`` runs; None where none runs at all."""
        n = sum(1 for p in ("forward", "recompute", "backward")
                if self.booked["by_scope_pass"].get(f"{scope}|{p}", 0.0) > 0)
        return n or None

    def notes(self, reader_s: float):
        b = self.booked
        rank = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
        note("train_scopes", steps=self.steps, busy_s=b["busy_s"],
             op_s=b["op_s"], by_scope=rank(b["by_scope"]),
             by_pass={p: b["by_pass"].get(p, 0.0) for p in PASSES},
             by_scope_pass=rank(b["by_scope_pass"]),
             by_scope_group=rank(b["by_scope_group"]),
             collectives=dict(sorted(b["collectives"].items())),
             unscoped_top=sorted(b["unscoped"].items(),
                                 key=lambda kv: -kv[1])[:6],
             idle_s=self.idle["idle_s"],
             idle_by_span=rank(self.idle["by_span"]), reader_s=reader_s)


def of(rec):
    """The ``Booked`` of a traced training run, computed once (and its
    line printed once); None where there is no trace, no device
    operation, or no operation under a training scope.  The window is
    the one ``trace.reduce`` cut (``rec["trace"]``, which run.py fills
    before any reader runs)."""
    if "_train_scopes" not in rec:
        booked = None
        path = trace.find_xplane(rec["trace_dir"]) \
            if rec.get("kind") == "train" and rec.get("trace_dir") else None
        if path:
            t0 = time.perf_counter()
            threads, ops, op_names = program_spans.read(path)
            if ops:
                booked = Booked(
                    threads, ops, op_names,
                    (rec.get("trace") or {}).get("window"),
                    (rec.get("config") or {}).get("trace_groups"))
                if booked.scoped:
                    booked.notes(time.perf_counter() - t0)
                else:
                    booked = None
        rec["_train_scopes"] = booked
    return rec["_train_scopes"]
