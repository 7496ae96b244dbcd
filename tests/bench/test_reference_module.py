"""A model's plain reference is the module its configuration names: the
default when it names none, and a module added as a new file, with no
existing file edited, is the one both harnesses draw weights from, compare
against and count operations with."""
import copy
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, federation, flops, model, serving

SEED = 2**31 + 77
DENSE = "bench/references/dense.py"
TOY = "bench/references/toy_logged.py"
LOGGED = '''"""The dense reference, each call it answers logged in CALLS."""
from bench import model

CALLS = []
_dense = model.backbone({})


def _logged(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(_dense, name)(*args, **kwargs)
    return call


for _name in ("backbone_shapes", "lora_dims", "forward", "forward_at",
              "matmul_weights", "pair_flops", "kv_layers", "live_kv_bytes"):
    globals()[_name] = _logged(_name)
'''


def test_no_reference_key_is_the_dense_module_bit_for_bit(tiny_fed):
    _, conf, _, _ = tiny_fed
    m = conf["clients"]["model"]
    named = dict(m, reference=DENSE)
    assert "reference" not in m
    assert model.backbone(m) is model.backbone(named)
    key = jax.random.key(SEED % 2**31)
    a = jax.jit(lambda k: model.draw_model(k, m, 0.02))(key)
    b = jax.jit(lambda k: model.draw_model(k, named, 0.02))(key)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        2, m["vocab_size"], (2, 12)), jnp.int32)
    pre = jnp.ones((2, m["n_soft_tokens"], m["d_model"]), jnp.float32)

    def logits(mm, w):
        p = model.nest({k: v for k, v in w.items()
                        if not k.startswith("connector/")})
        return np.asarray(model.backbone(mm).forward(p, mm, toks, pre))
    assert np.array_equal(logits(m, a), logits(named, b))
    assert flops.round_flops(conf, tiny_fed[2]) == flops.round_flops(
        dict(conf, clients=dict(conf["clients"], model=named)), tiny_fed[2])


def test_reference_outside_bench_is_refused():
    with pytest.raises(ValueError):
        model.backbone({"reference": "src/repro/models/model.py"})


@pytest.fixture
def logged_root(tmp_path, monkeypatch):
    """A checkout whose ``bench/`` holds one new file, a reference module
    that logs its calls."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(common.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), root)
    (root / TOY).write_text(LOGGED)
    monkeypatch.setattr(common, "ROOT", str(root))
    monkeypatch.setattr(common, "BENCH_DIR", str(root / "bench"))
    return root


def _named(m):
    return dict(m, reference=TOY)


def test_a_new_reference_module_serves_both_harnesses(
        logged_root, tiny_fed, tiny_serve):
    w, conf, job, limits = copy.deepcopy(tiny_fed)
    conf["clients"]["model"] = _named(conf["clients"]["model"])
    conf["server_llm"] = _named(conf["server_llm"])
    mod = model.backbone(conf["server_llm"])
    assert mod.__file__ == str(logged_root / TOY)
    res, checks = federation.run(w, conf, job, dict(limits, grad_gap=0.05,
                                                    change_gap=0.05),
                                 SEED, 1.0, 0, jax.devices()[:1],
                                 time.perf_counter())
    assert res["correct"], checks
    fed_calls = set(mod.CALLS)
    assert {"backbone_shapes", "lora_dims", "forward"} <= fed_calls

    del mod.CALLS[:]
    w, sconf, traffic, slimits = copy.deepcopy(tiny_serve)
    sconf["model"] = _named(sconf["model"])
    res, checks = serving.run(w, sconf, traffic, dict(
        slimits, checked_tokens=40, logit_gap=0.05), SEED, 2.0, 0,
        jax.devices()[:1], time.perf_counter())
    assert res["correct"], checks
    assert {"backbone_shapes", "lora_dims", "forward_at"} <= set(mod.CALLS)

    del mod.CALLS[:]
    m = sconf["model"]
    plain = dict(m)
    del plain["reference"]
    assert flops.round_flops(conf, job) > 0
    assert flops.decode_flops(m, 100) == flops.decode_flops(plain, 100)
    assert flops.live_kv_bytes(m, [100, 17], 16) == \
        flops.live_kv_bytes(plain, [100, 17], 16)
    assert flops.kv_layers(m) == m["n_layers"]
    assert {"matmul_weights", "pair_flops", "live_kv_bytes",
            "kv_layers"} <= set(mod.CALLS)
