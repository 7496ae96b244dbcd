"""Reduction of the program's own spans and named scopes in a profiler trace,
beside ``bench/trace.py``'s reduction of busy time, programs and operations.

The program writes host spans ``fed.<step>`` and ``serve.<step>`` with their
arguments, and names the layers of its traced functions with
``jax.named_scope``; a device operation carries the scopes in its op name
(``jit(round_fn)/device_phase/ccl/while/body/...``).  The profiler writes the
trace twice, as the ``.xplane.pb`` that ``bench/trace.py`` reads and as a
``.trace.json.gz`` beside it, and only the second holds the operations' op
names (``tf_op``), so this module reads that one.  From device 0:

- idle per span: every gap between its busy intervals, and the gap from the
  first host span's start to its first operation, split over time among the
  innermost host spans open in it (the program's and the harness's own
  ``run_round``, ``sync``, ``submit``, ``tick``, ``wait``); time under no span
  is ``other``;
- device seconds per scope: each leaf operation's time under the path of the
  known scopes in its op name (``device_phase/ccl``), ``""`` under none.
"""
from __future__ import annotations

import gzip
import json
import os
import re

from bench import common, trace

SCOPES = ("device_phase", "ccl", "amt", "channel", "mma", "server_phase",
          "redistribute")
PROGRAM_SPANS = ("fed.", "serve.")
DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_WRAP = re.compile(r"^([\w.]+)\((.*)\)$")


def scope_path(op_name: str) -> str:
    """The known scopes in an op name, outermost first: transformations
    around a scope (``transpose(jvp(mma))``) are looked through, program
    names (``jit(server_phase)``) are not scopes."""
    out = []
    for part in op_name.split("/"):
        m = _WRAP.match(part)
        while m and m.group(1) != "jit":
            part = m.group(2)
            m = _WRAP.match(part)
        if not m and part in SCOPES:
            out.append(part)
    return "/".join(out)


def _arg(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return v


def load(path: str) -> tuple:
    """(ops, spans) of a ``.trace.json.gz``: each device's XLA operations
    as {device number: [(op name, start ns, end ns)]}, and the host spans
    (the program's and the harness's) as (name, start ns, end ns,
    {argument: value})."""
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    lines = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    ops, spans = {}, []
    for e in events:
        if e.get("ph") != "X":
            continue
        a = int(round(e["ts"] * 1e3))
        b = a + int(round(e.get("dur", 0) * 1e3))
        proc = procs.get(e["pid"], "")
        dev = DEVICE.match(proc)
        if dev and lines.get((e["pid"], e["tid"])) == "XLA Ops":
            ops.setdefault(int(dev.group(1)), []).append(
                (e.get("args", {}).get("tf_op", ""), a, b))
        elif proc.startswith("/host:CPU") and (
                e["name"] in trace.HOST_SPANS
                or e["name"].startswith(PROGRAM_SPANS)):
            spans.append((e["name"], a, b,
                          {k: _arg(v) for k, v in e.get("args", {}).items()}))
    return ops, spans


def _innermost(spans) -> list:
    """[(start, end, name)] pieces of time, each labelled with the shortest
    span open over it."""
    cuts = sorted({t for _, a, b, _ in spans for t in (a, b)})
    by_start = sorted(spans, key=lambda s: s[1])
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [s for s in open_ if s[2] >= b]
        if open_:
            out.append((a, b, min(open_, key=lambda s: s[2] - s[1])[0]))
    return out


def reduce(ops, spans) -> dict:
    """Busy and idle seconds of device 0, the idle seconds under each
    innermost host span, the device seconds per device under each path of
    scopes, and the program's own spans (name, start ns, end ns,
    arguments)."""
    scope_s: dict = {}
    for dev_ops in ops.values():
        for name, a, b in trace._leaves(dev_ops):
            k = scope_path(name)
            scope_s[k] = scope_s.get(k, 0.0) + (b - a) * 1e-9
    scope_s = {k: v / max(1, len(ops)) for k, v in scope_s.items()}
    busy = trace._union(ops.get(0, []))
    gaps = [(x[1], y[0]) for x, y in zip(busy, busy[1:])]
    if busy and spans:
        first = min(a for _, a, _, _ in spans)
        if first < busy[0][0]:
            gaps.insert(0, (first, busy[0][0]))
    idle: dict = {}
    pieces, j = _innermost(spans), 0
    for a, b in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                name = pieces[k][2]
                idle[name] = idle.get(name, 0) + hi - lo
                covered += hi - lo
            k += 1
        if b - a > covered:
            idle["other"] = idle.get("other", 0) + b - a - covered
    return {"busy_s": sum(b - a for a, b in busy) * 1e-9,
            "idle_s": sum(b - a for a, b in gaps) * 1e-9,
            "idle_by_span": {k: v * 1e-9 for k, v in idle.items()},
            "scope_s": scope_s,
            "spans": [s for s in spans if s[0].startswith(PROGRAM_SPANS)]}


def trace_json(cell: str) -> str | None:
    """The ``.trace.json.gz`` the harness's profiler wrote for ``cell``."""
    xplane = common.Profiler(True, cell).xplane()
    if xplane is None:
        return None
    d = os.path.dirname(xplane)
    found = sorted(f for f in os.listdir(d) if f.endswith(".trace.json.gz"))
    return os.path.join(d, found[0]) if found else None


def of(ctx: dict) -> dict | None:
    """The reduction of the traced slice of ``ctx``'s run, made once and kept
    in ``ctx`` for the other readers of the run; None without a trace."""
    if "spans" not in ctx:
        path = (trace_json(ctx["cell"])
                if ctx.get("trace") is not None else None)
        ctx["spans"] = reduce(*load(path)) if path else None
    return ctx["spans"]


def scope_seconds(summary: dict, scope: str) -> float:
    """Device seconds of the operations whose outermost scope is ``scope``."""
    return sum(v for k, v in summary["scope_s"].items()
               if k.split("/")[0] == scope)


def scope_ms_per_round(ctx: dict, scope: str) -> float | None:
    """Device milliseconds per traced round under ``scope``; None where no
    operation carries it."""
    s = of(ctx)
    if s is None or not ctx.get("rounds_traced"):
        return None
    sec = scope_seconds(s, scope)
    return sec / ctx["rounds_traced"] * 1e3 if sec > 0 else None


def idle_ms_per_round(ctx: dict, names) -> float | None:
    """Device-idle milliseconds per traced round whose innermost host span
    is one of ``names``; None where the trace holds no device operation or
    the program wrote no ``fed.round`` span."""
    s = of(ctx)
    if s is None or not ctx.get("rounds_traced") or not s["busy_s"] \
            or not named(s, "fed.round"):
        return None
    idle = sum(s["idle_by_span"].get(n, 0.0) for n in names)
    return idle / ctx["rounds_traced"] * 1e3


def named(summary: dict, name: str) -> list:
    """The program's spans called ``name``: (start ns, end ns, arguments)."""
    return [(a, b, args) for n, a, b, args in summary["spans"] if n == name]
