"""Pieces every cell shares: the manifest and its files, the device check,
compile counting, host spans, the profiler window and the result line."""
from __future__ import annotations

import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import jax

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The accelerator the cell needs is not there."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache where the program places it
    (``repro.launch.mesh.use_compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``) and write every program to it, however
    fast it compiled.  JAX writes by default only programs that took a
    second or more, so every run would compile the small ones again (the
    serving cell's read-backs, the eager steps around a split round) and
    set-up would not find every program in the cache from its second run
    on.  It also makes the MMA scan that the split schedule traces anew
    each round a cache load rather than a compile (PERF.md, sections 2 and
    7)."""
    from repro.launch.mesh import use_compile_cache as place
    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def manifest() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> tuple:
    """(workload entry, configuration dict, traffic dict, limits dict) of a
    workload named in ``BENCHMARK.json``, each read from its own file."""
    man = manifest()
    wl = {w["name"]: w for w in man["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg = {c["name"]: c for c in man["configs"]}[w["config"]]
    conf = load_json(ROOT, cfg["file"])
    traffic = load_json(BENCH_DIR, "traffic", w["traffic"] + ".json")
    limits = load_json(BENCH_DIR, "limits", name + ".json")
    return w, conf, traffic, limits


def load_file(path: str):
    """The module in the Python file ``path``, loaded once a process."""
    name = "bench_file_" + hashlib.sha1(path.encode()).hexdigest()[:16]
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_file(os.path.join(BENCH_DIR, "metrics", name + ".py")).read


def check_device(chips: int):
    """The devices JAX sees, or :class:`NoDevice` where they are not TPUs
    or fewer than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"{chips} chips asked, JAX found {len(devs)}")
    return devs[:chips]


def memory_peaks(devs) -> list:
    """Each device's peak bytes in use so far (0 where not reported)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(memory_peaks(devs))}


class CompileCounter:
    """Counts JAX backend compiles (or persistent-cache loads) while on."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.n += 1
            self.s += secs

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """Traces one slice of the window into ``<checkout>/.bench_trace``."""

    def __init__(self, on: bool, tag: str):
        self.on = on
        self.dir = os.path.join(ROOT, ".bench_trace", tag)
        self.t0 = self.t1 = None
        self.running = False

    def start(self):
        if self.on and self.t0 is None:
            if os.path.isdir(self.dir):
                shutil.rmtree(self.dir)
            jax.profiler.start_trace(self.dir)
            self.running = True
            self.t0 = time.perf_counter()

    def stop(self):
        if self.running:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.running = False

    def xplane(self) -> str | None:
        for d, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(d, f)
        return None


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation; +inf entries count as
    missing the limit."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = q * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if v[hi] == math.inf:
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def report(result: dict, checks: list) -> None:
    """Print the compared numbers (last lines of stderr) and the result
    line (last line of stdout).  ``checks`` holds (name, value, limit)."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)


def end_to_end(cell: str, values: dict) -> dict:
    """The result line's metrics: the manifest's end-to-end metrics that
    this cell reports, from ``values`` (name -> number or None)."""
    out = {}
    for e in manifest()["end_to_end"]:
        v = values.get(e["name"])
        if v is not None and cell in e.get("workloads", [cell]):
            out[e["name"]] = {"value": v, "unit": e["unit"]}
    return out


def collect_per_layer(w, ctx, summary) -> dict:
    """Run each per-layer reader that lists this cell (or lists none and
    moves an end-to-end metric this cell reports); readers that find
    nothing return None and are left out.  ``_device`` and ``_breakdown``
    carry the trace's busy and window seconds and its breakdown."""
    man = manifest()
    e2e_here = {e["name"] for e in man["end_to_end"]
                if w["name"] in e.get("workloads", [w["name"]])}
    out = {}
    for pm in man["per_layer"]:
        wanted = (w["name"] in pm["workloads"] if "workloads" in pm
                  else pm["moves"] in e2e_here)
        if not wanted:
            continue
        v = metric_reader(pm["name"])(ctx)
        if v is not None:
            out[pm["name"]] = {"value": v, "unit": pm["unit"]}
    if summary is not None:
        out["_device"] = {"busy_s": summary["busy_s"],
                          "window_s": summary["window_s"]}
        out["_breakdown"] = summary["breakdown"]
    else:
        out["_device"], out["_breakdown"] = {}, None
    return out
