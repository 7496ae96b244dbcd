"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device planes are those named ``/device:TPU:<n>``.  On each, the line of
XLA operations gives the busy intervals (their union), the per-operation
time and the kernels; the line of XLA modules gives the time of each
compiled program.  The host plane's events with the benchmark's span names
say what the host was doing in each idle gap of device 0.
"""
from __future__ import annotations

import re

HOST_SPANS = ("run_round", "sync", "submit", "tick", "wait")
_SUFFIX = re.compile(r"\(\d+\)$")


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(iv):
    iv = sorted((a, b) for _, a, b in iv)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def program_name(name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``."""
    return _SUFFIX.sub("", name)


_OP = re.compile(r"^%?([A-Za-z_\-]+?)(?:\.\d+)* = ")
_KERNEL = re.compile(r'kernel_name="([^"]+)"|"name":\s*"([^"]+)"')


def op_label(name: str) -> str:
    """A readable label of an XLA op event: its kind (``fusion``,
    ``copy``...), and for a custom call the kernel's name where the op's
    text carries it."""
    m = _OP.match(name)
    label = m.group(1) if m else name[:60]
    if label.startswith("custom-call"):
        k = _KERNEL.search(name)
        if k:
            label = f"custom-call:{k.group(1) or k.group(2)}"
    return label


def _leaves(events):
    """Events that contain no other event of the line (loop and call ops
    contain their bodies' ops)."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, a, b) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < b:
            continue
        out.append((n, a, b))
    return out


def reduce(planes: list, window_s: float, top: int = 10) -> dict:
    """``planes``: [(plane name, {line name: [(event, start_ns, end_ns)]})].
    Returns busy and window seconds (busy averaged over the devices), the
    number of devices, device seconds and call counts (summed over devices)
    per program and per operation label (of leaf operations: a loop's time
    is its body's), and the breakdown."""
    devices = [(n, lines) for n, lines in planes
               if re.match(r"^/device:TPU:\d+$", n)]
    hosts = [lines for n, lines in planes if n.startswith("/host:CPU")]
    programs, ops = {}, {}
    busy_total, gaps = 0.0, []
    for i, (_, lines) in enumerate(devices):
        op_line = lines.get("XLA Ops", [])
        mod_line = lines.get("XLA Modules", [])
        for name, a, b in _leaves(op_line):
            lab = op_label(name)
            s, c = ops.get(lab, (0.0, 0))
            ops[lab] = (s + (b - a) * 1e-9, c + 1)
        for name, a, b in mod_line:
            k = program_name(name)
            s, c = programs.get(k, (0.0, 0))
            programs[k] = (s + (b - a) * 1e-9, c + 1)
        busy = _union(op_line or mod_line)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        if i == 0:
            gaps = [(busy[j][1], busy[j + 1][0])
                    for j in range(len(busy) - 1)]
    n_dev = max(1, len(devices))
    spans = [(n, a, b) for lines in hosts for evs in lines.values()
             for n, a, b in evs if n in HOST_SPANS]

    def doing(a, b):
        mid = (a + b) / 2
        best = None
        for n, s, e in spans:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "other"

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    return {"busy_s": busy_total / n_dev, "window_s": window_s,
            "devices": n_dev, "programs": programs, "labels": ops,
            "breakdown": {
                "device_ops": [[k, v[0] / n_dev] for k, v in top_ops],
                "idle_gaps": [[doing(a, b), (b - a) * 1e-9]
                              for a, b in gaps]}}


def load(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(p.name, {ln.name: _events(ln) for ln in p.lines})
            for p in pd.planes]


def summarize(path: str, prof) -> dict:
    """The reduction of the trace a :class:`bench.common.Profiler` took."""
    return reduce(load(path), prof.t1 - prof.t0)


def time_of(summary: dict, pattern: str, table: str = "programs"):
    """(seconds, calls) per device of the programs (``table="programs"``)
    or operation labels (``"labels"``) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    s = c = 0
    for k, (sec, n) in summary[table].items():
        if rx.search(k):
            s, c = s + sec, c + n
    return s / summary["devices"], c / summary["devices"]
