"""The reduction of the program's spans and named scopes
(``bench/spans.py``) on small written traces, the per-layer readers built
on it, and a traced serving run at toy widths on the CPU, where the
engine's own counters meet the harness's ledger."""
import gzip
import json
import os
import time

import jax
import pytest

from bench import common, serving, spans

SEED = 2**31 + 91

FED = [  # (name, start us, end us, arguments) of the host spans of a round
    ("run_round", 0, 1000, {}), ("fed.round", 10, 990, {"round": 4}),
    ("fed.begin", 20, 30, {"round": 4}), ("fed.assemble", 30, 100, {}),
    ("fed.dispatch", 100, 120, {"cohort": 0}), ("fed.decode", 400, 450, {}),
    ("fed.combine", 450, 700, {}), ("fed.server_phase", 700, 710, {}),
    ("fed.deliver", 900, 960, {}), ("fed.scatter", 960, 980, {}),
    ("sync", 1000, 1300, {})]
CCL = "jit(round_fn)/device_phase/ccl/while/body/closed_call/"
# a loop op holding its body's ops; ten AMT ops with nine 3 us gaps between
# them (gaps below the breakdown's top ten); the server's and the
# redistribution's ops
OPS = ([("jit(round_fn)/device_phase/ccl/while", 110, 200),
        (CCL + "transpose(jvp(vmap()))/dot_general:", 110, 150),
        (CCL + "vmap(jvp())/add:", 150, 200)]
       + [("jit(round_fn)/device_phase/amt/while/body/mul:", 200 + 19 * k,
           216 + 19 * k) for k in range(10)]
       + [("jit(round_fn)/server_phase/while/body/dot_general:", 720, 880),
          ("jit(round_fn)/redistribute/broadcast_in_dim:", 965, 970)])
# idle (us) under each innermost span: the slice starts with the first span
IDLE = {"run_round": 10, "fed.round": 10 + 27 + 13 + 10 + 20, "fed.begin": 10,
        "fed.assemble": 70, "fed.dispatch": 10, "fed.decode": 50,
        "fed.combine": 250, "fed.server_phase": 10, "fed.deliver": 60,
        "fed.scatter": 5}


def _write(root, cell, ops, host, devices=1):
    """A profiler trace of ``cell`` where the harness's profiler keeps it:
    the ``.xplane.pb`` it looks for and the ``.trace.json.gz`` beside it.
    ``ops`` is one device's list, or a list of lists, one a device."""
    d = os.path.join(root, ".bench_trace", cell, "plugins", "profile", "t")
    os.makedirs(d)
    open(os.path.join(d, "h.xplane.pb"), "wb").close()
    per_dev = ops if ops and isinstance(ops[0], list) else [ops] * devices
    ev = [{"ph": "M", "pid": 701, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    for i, dev_ops in enumerate(per_dev):
        pid = 3 + i
        ev += [{"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": f"/device:TPU:{i}"}},
               {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
                "args": {"name": "XLA Modules"}},
               {"ph": "M", "pid": pid, "tid": 3, "name": "thread_name",
                "args": {"name": "XLA Ops"}},
               {"ph": "X", "pid": pid, "tid": 2, "ts": 0, "dur": 2000,
                "name": "jit_round_fn(1)", "args": {}}]
        ev += [{"ph": "X", "pid": pid, "tid": 3, "ts": a, "dur": b - a,
                "name": "fusion.1", "args": {"tf_op": tf}}
               for tf, a, b in dev_ops]
    ev += [{"ph": "X", "pid": 701, "tid": 9, "ts": a, "dur": b - a,
            "name": n, "args": {k: str(v) for k, v in args.items()}}
           for n, a, b, args in host]
    with gzip.open(os.path.join(d, "h.trace.json.gz"), "wt") as f:
        json.dump({"displayTimeUnit": "ns", "traceEvents": ev}, f)


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    return str(tmp_path)


def _read(name, ctx):
    return common.metric_reader(name)(ctx)


def test_scope_path_looks_through_transformations_not_programs():
    assert spans.scope_path(CCL + "vmap(jvp())/dot_general:") == \
        "device_phase/ccl"
    assert spans.scope_path(
        "jit(device_phase)/channel/pjit(quantize)/x") == "channel"
    assert spans.scope_path("jit(round_fn)/while/transpose(jvp(mma))/y") \
        == "mma"
    assert spans.scope_path("jit(server_phase)/while/body/add") == ""
    assert spans.scope_path("") == ""


def test_reduction_splits_every_gap_among_innermost_spans(trace_root):
    _write(trace_root, "fed720m.split_int8", OPS, FED)
    s = spans.reduce(*spans.load(spans.trace_json("fed720m.split_int8")))
    assert s["busy_s"] == pytest.approx(415e-6)
    assert s["idle_s"] == pytest.approx(555e-6)
    assert s["idle_by_span"] == pytest.approx(
        {k: v * 1e-6 for k, v in IDLE.items()})
    assert s["scope_s"] == pytest.approx(
        {"device_phase/ccl": 90e-6, "device_phase/amt": 160e-6,
         "server_phase": 160e-6, "redistribute": 5e-6})
    assert spans.scope_seconds(s, "device_phase") == pytest.approx(250e-6)
    # the program's spans keep their arguments; the harness's are left out
    assert [n for n, *_ in s["spans"]] == [n for n, *_ in FED
                                           if n.startswith("fed.")]
    assert spans.named(s, "fed.round")[0][2] == {"round": 4.0}


def test_fed_readers_per_traced_round(trace_root):
    _write(trace_root, "fed720m.split_int8", OPS, FED)
    ctx = {"cell": "fed720m.split_int8", "trace": {}, "rounds_traced": 2}
    want = {"device_phase.ms": 250e-3, "server_phase.ms": 160e-3,
            "assemble.idle_ms": 85e-3, "combine.idle_ms": 300e-3,
            "deliver.idle_ms": 60e-3}
    got = {n: _read(n, ctx) for n in want}
    assert got == pytest.approx({n: v / 2 for n, v in want.items()})
    assert ctx["spans"]["busy_s"] > 0          # reduced once, for every reader


def test_fed_readers_read_per_chip_on_four_devices(trace_root):
    """Four devices: the scopes' device time is each device's, averaged;
    the idle under the host spans is device 0's, as on one device."""
    # devices 1-3 ran the round with half of the server phase's time
    half = [(n, a, b if "server_phase" not in n else a + (b - a) // 2)
            for n, a, b in OPS]
    _write(trace_root, "fed720m.fused.4chip", [OPS, half, half, half], FED)
    ctx = {"cell": "fed720m.fused.4chip", "trace": {}, "rounds_traced": 2}
    assert _read("device_phase.ms", ctx) == pytest.approx(250e-3 / 2)
    assert _read("server_phase.ms", ctx) == pytest.approx(
        (160 + 3 * 80) / 4 * 1e-3 / 2)
    assert _read("assemble.idle_ms", ctx) == pytest.approx(85e-3 / 2)
    assert ctx["spans"]["idle_by_span"] == pytest.approx(
        {k: v * 1e-6 for k, v in IDLE.items()})


def test_fed_readers_find_nothing_in_a_trace_without_program_spans(
        trace_root):
    """The parent's program: the harness's spans and no scopes."""
    _write(trace_root, "fed720m.fused",
           [("jit(round_fn)/while/body/add:", a, b) for _, a, b in OPS],
           [s for s in FED if not s[0].startswith("fed.")])
    ctx = {"cell": "fed720m.fused", "trace": {}, "rounds_traced": 1}
    for n in ("device_phase.ms", "server_phase.ms", "assemble.idle_ms",
              "combine.idle_ms", "deliver.idle_ms", "admit.ms",
              "admission.queue_wait_p50_ms", "slots.occupancy"):
        assert _read(n, dict(ctx, conf={"engine": {"n_slots": 32}})) is None
    # and without a trace at all
    assert _read("device_phase.ms", {"cell": "fed720m.fused",
                                     "trace": None}) is None


def test_serving_readers_on_the_engine_spans(trace_root):
    host = [("tick", 0, 100, {}), ("serve.tick", 1, 99, {"tick": 7}),
            ("serve.admit", 5, 45, {"rid": 3, "queued_us": 2000}),
            ("serve.prefill", 6, 30, {}), ("serve.insert", 31, 44, {}),
            ("serve.step", 46, 98, {"busy": 3}),
            ("wait", 100, 150, {}), ("submit", 150, 152, {}),
            ("tick", 152, 300, {}), ("serve.tick", 153, 299, {"tick": 8}),
            ("serve.admit", 155, 215, {"rid": 4, "queued_us": 9000}),
            ("serve.admit", 215, 255, {"rid": 5, "queued_us": 3000}),
            ("serve.step", 256, 298, {"busy": 5})]
    _write(trace_root, "serve720m.chat",
           [("jit(step)/while/body/add:", 46, 98),
            ("jit(step)/while/body/add:", 256, 298)], host)
    ctx = {"cell": "serve720m.chat", "trace": {},
           "conf": {"engine": {"n_slots": 32}}}
    assert _read("admit.ms", ctx) == pytest.approx((40 + 60 + 40) / 3e3)
    assert _read("admission.queue_wait_p50_ms", ctx) == pytest.approx(3.0)
    assert _read("slots.occupancy", ctx) == pytest.approx(
        100 * (3 + 5) / (2 * 32))
    idle = ctx["spans"]["idle_by_span"]
    assert idle == pytest.approx({
        "tick": 3e-6, "serve.tick": 9e-6, "serve.admit": 103e-6,
        "serve.prefill": 24e-6, "serve.insert": 13e-6, "wait": 50e-6,
        "submit": 2e-6})


def test_traced_serving_run_reads_the_engine_and_agrees_with_the_ledger(
        tiny_serve, monkeypatch):
    """At toy widths on the CPU: the engine's spans reach the readers
    through the harness's own trace, and over the window and its drain the
    engine counts as many admissions as the ledger, and as many busy
    slot-steps as the ledger's ticks from admission to finish."""
    window = serving.serve_window
    seen = {}

    def counted(engine, *args):
        before = engine.stats()
        out = window(engine, *args)
        seen.update(before=before, after=engine.stats(), led=out[0],
                    n_sub=out[3])
        return out
    monkeypatch.setattr(serving, "serve_window", counted)
    # the engine's readers alone: the others read device planes and peaks,
    # which a CPU trace has not
    man = common.manifest()
    mine = ("admit.ms", "admission.queue_wait_p50_ms", "slots.occupancy")
    monkeypatch.setattr(common, "manifest", lambda: dict(man, per_layer=[
        p for p in man["per_layer"] if p["name"] in mine]))
    w, conf, traffic, limits = tiny_serve
    res, checks = serving.run(w, conf, traffic, limits, SEED, 2.0, 1,
                              jax.devices()[:1], time.perf_counter())
    assert res["correct"], checks
    for n in mine:
        assert res["metrics"][n]["value"] > 0, n
    assert res["metrics"]["slots.occupancy"]["value"] <= 100
    led, n_sub = seen["led"], seen["n_sub"]
    before, after = seen["before"], seen["after"]
    adm, fin = led.adm[:n_sub], led.fin[:n_sub]
    assert (fin >= 0).all()
    assert after["admissions"] - before["admissions"] == (adm >= 0).sum()
    m = conf["model"]
    max_new = serving.generator.requests(
        traffic, serving.model.np_seed(SEED, 2), 2.0, m["vocab_size"],
        m["n_modalities"], m["modality_dim"])["max_new"]
    held = max_new[:n_sub] >= 2          # the others finish at admission
    assert after["slot_steps"] - before["slot_steps"] == \
        int((fin - adm + 1)[held].sum())
