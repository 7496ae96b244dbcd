"""The program's own tracing: the federated round's ``fed.*`` host spans and
the named scopes its traced functions give their device ops, the serving
engine's ``serve.*`` spans, its ``stats()`` counters and request times, and
the compile cache keyed on the ops' metadata."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.channel import ChannelSpec
from repro.core.federated import FederatedRunner
from repro.core.spec import ClientCohort, FederationSpec
from repro.data.synthetic import synthetic_multimodal_corpus
from repro.launch.serve_engine import EngineConfig, ServingEngine
from repro.models.model import build_model

_KW = dict(n_modalities=3, modality_dim=32, n_soft_tokens=4, connector_dim=48,
           lora_rank=4, remat=False, activation="gelu", vocab_size=128)


def _model(name, d, hd, ff):
    return ModelConfig(name=name, family="dense", n_layers=1, d_model=d,
                       n_heads=2, n_kv_heads=2, head_dim=hd, d_ff=ff, **_KW)


def _runner(robust):
    """Two toy clients with the int8 error-feedback channel: ``norm_clip``
    takes the split schedule, ``mean`` the fused round."""
    spec = FederationSpec(
        cohorts=(ClientCohort(model=_model("tr-slm", 32, 8, 64),
                              n_clients=2),),
        server_llm=_model("tr-llm", 64, 16, 96), engine="vectorized",
        rounds=2, local_steps_ccl=1, local_steps_amt=1, server_steps=1,
        batch_size=4, lr=1e-2, rho=0.7, seed=0, robust=robust,
        channel=ChannelSpec(codec="int8"))
    corpus = synthetic_multimodal_corpus(0, 128, 20, 128, n_classes=4,
                                         n_modalities=3, modality_dim=32,
                                         template_len=4)
    return FederatedRunner(spec, corpus)


def _host_spans(logdir, prefix):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_split_round_writes_its_steps_in_order_under_fed_round(tmp_path):
    runner = _runner("norm_clip")
    assert not runner._fused
    runner.run_round(evaluate=False)          # compiles
    runner.sync()
    jax.profiler.start_trace(str(tmp_path))
    runner.run_round(evaluate=False)
    runner.sync()
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "fed.")
    (rnd,) = [s for s in spans if s[0] == "fed.round"]
    assert rnd[3]["round"] == 1
    steps = [s for s in spans if s[0] != "fed.round"]
    assert all(_inside(s, rnd) and s[3]["round"] == 1 for s in steps)
    order = [s[0] for s in steps]
    want = ["fed.begin", "fed.assemble", "fed.dispatch", "fed.decode",
            "fed.combine", "fed.server_phase", "fed.deliver", "fed.scatter"]
    assert [n for n in order if n in want] == want, order
    (dispatch,) = [s for s in steps if s[0] == "fed.dispatch"]
    assert dispatch[3]["cohort"] == 0


def _lowered_text(fn, args):
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, jax.Array) else x, args)
    return fn.lower(*shapes).as_text(debug_info=True)


def _capture(runner, attr):
    """Run a round with ``runner.<attr>`` wrapped to keep the lowered text
    of its call (its arguments' shapes, before they are donated)."""
    fn, seen = getattr(runner, attr), []

    def wrapped(*args):
        seen.append(_lowered_text(fn, args))
        return fn(*args)
    setattr(runner, attr, wrapped)
    runner.run_round(evaluate=False)
    setattr(runner, attr, fn)
    return seen[0]


def test_device_ops_carry_the_layer_scopes_in_both_schedules():
    fused = _runner("mean")
    assert fused._fused
    txt = _capture(fused, "_round_fn")
    for scope in ("device_phase/ccl", "device_phase/amt", "channel", "mma",
                  "server_phase", "redistribute"):
        assert f'"jit(round_fn)/{scope}/' in txt, scope
    split = _runner("norm_clip")
    dev = split._device_phase_fns[0]
    seen = []

    def wrapped(*args):
        seen.append(_lowered_text(dev, args))
        return dev(*args)
    split._device_phase_fns[0] = wrapped
    srv = _capture(split, "_server_phase_fn")
    for scope in ("device_phase/ccl", "device_phase/amt", "channel"):
        assert f'"jit(device_phase)/{scope}/' in seen[0], scope
    assert '"jit(server_phase)/server_phase/' in srv
    # the programs keep the names the benchmark's readers look for
    assert "@jit_round_fn" in txt and "@jit_device_phase" in seen[0]
    assert "@jit_server_phase" in srv


def test_a_scope_change_does_not_load_the_unscoped_executable():
    """The persistent cache keys on the ops' metadata: a program that
    differs from a cached one only in a named scope compiles with it."""
    def make(scoped):
        def f(x):
            if scoped:
                with jax.named_scope("cache_probe_scope"):
                    return jnp.tanh(x) @ x.T
            return jnp.tanh(x) @ x.T
        return f

    x = jnp.ones((24, 24))
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        jax.jit(make(False)).lower(x).compile()
        txt = jax.jit(make(True)).lower(x).compile().as_text()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old)
    assert "cache_probe_scope" in txt


# ---------------------------------------------------------------------------
# serving engine

def _engine(**kw):
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=64, n_modalities=0, remat=False, lora_rank=2,
                      dtype="float32")
    b = build_model(cfg)
    econf = EngineConfig(**dict(dict(n_slots=2, page_size=4, n_pages=32,
                                     max_pages_per_seq=4, max_out=8,
                                     buckets=(8,)), **kw))
    return ServingEngine(b, b.init(jax.random.key(0)), econf)


def _prompt(seed, n=5):
    return np.random.RandomState(seed).randint(0, 64, (n,)).astype(np.int32)


def test_admission_span_holds_prefill_and_insert(tmp_path):
    engine = _engine()
    engine.submit(_prompt(0), max_new=3)
    engine.run()                               # compiles
    rid = engine.submit(_prompt(1), max_new=3)
    jax.profiler.start_trace(str(tmp_path))
    engine.tick()
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "serve.")
    (tick,) = [s for s in spans if s[0] == "serve.tick"]
    (admit,) = [s for s in spans if s[0] == "serve.admit"]
    (prefill,) = [s for s in spans if s[0] == "serve.prefill"]
    (insert,) = [s for s in spans if s[0] == "serve.insert"]
    (step,) = [s for s in spans if s[0] == "serve.step"]
    assert admit[3]["rid"] == rid and admit[3]["bucket"] == 8
    assert admit[3]["pages"] == 3 and admit[3]["queued_us"] >= 0
    assert tick[3]["tick"] == engine.n_ticks - 1
    assert _inside(prefill, admit) and _inside(insert, admit)
    assert prefill[2] <= insert[1]
    assert _inside(admit, tick) and _inside(step, tick)
    assert admit[2] <= step[1] and step[3]["busy"] == 1


@pytest.mark.parametrize("n_pages,want", [
    # 31 free pages: the two slots bound admission
    (32, dict(admissions=4, admit_waits_pages=0, steps=4, slot_steps=6)),
    # 5 free pages, 3 a request: one at a time, the head waits on pages
    # in every tick but the last
    (6, dict(admissions=4, admit_waits_pages=6, steps=6, slot_steps=6)),
])
def test_stats_count_a_planned_schedule(n_pages, want):
    """Three requests of budget 3 (two decode steps each after the token
    the prefill samples) and one of budget 1, which finishes at admission,
    over two slots."""
    engine = _engine(n_pages=n_pages)
    rids = [engine.submit(_prompt(i), max_new=m)
            for i, m in enumerate((3, 3, 3, 1))]
    first = engine.stats()
    assert first == dict(queued=4, slots_busy=0, slots_total=2,
                         pages_free=n_pages - 1, pages_total=n_pages - 1,
                         admissions=0, admit_waits_pages=0, steps=0,
                         slot_steps=0)
    done = engine.run()
    got = engine.stats()
    assert {k: got[k] for k in want} == want
    assert got["queued"] == got["slots_busy"] == 0
    assert got["pages_free"] == got["pages_total"]
    for r in rids:
        q = done[r]
        assert q.t_submit <= q.t_admit <= q.t_first <= q.t_done
    assert done[rids[3]].t_first == done[rids[3]].t_done
