"""End-to-end behaviour tests for the ML-ECS system (Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.federated import FederatedConfig, FederatedRunner
from repro.data.synthetic import synthetic_multimodal_corpus
from repro.models.model import build_model

_KW = dict(n_modalities=3, modality_dim=32, n_soft_tokens=4,
           connector_dim=48, lora_rank=4, remat=False, activation="gelu",
           vocab_size=128)


def _bundles():
    slm = ModelConfig(name="sys-slm", family="dense", n_layers=2, d_model=48,
                      n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96, **_KW)
    llm = ModelConfig(name="sys-llm", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, **_KW)
    return build_model(slm), build_model(llm)


@pytest.fixture(scope="module")
def corpus():
    return synthetic_multimodal_corpus(0, 384, 24, 128, n_classes=4,
                                       n_modalities=3, modality_dim=32,
                                       template_len=4)


def _run(corpus, rounds=2, **overrides):
    slm, llm = _bundles()
    fc = FederatedConfig(n_devices=3, rounds=rounds, local_steps_ccl=2,
                         local_steps_amt=2, server_steps=2, batch_size=8,
                         lr=1e-2, rho=0.7, **overrides)
    runner = FederatedRunner(fc, slm, llm, corpus)
    pre = runner.evaluate()["summary"]
    hist = runner.run()
    return pre, hist[-1]["summary"], runner


@pytest.fixture(scope="module")
def protocol_run(corpus):
    """ONE full 2-round mlecs run shared by the system assertions below —
    compiling a fresh fused-round runner per test dominated the old
    suite's wall clock (~60 s of jit per test on the 2-core CI box)."""
    return _run(corpus, rounds=2)


def test_full_protocol_improves_clients_and_server(protocol_run):
    pre, post, _ = protocol_run
    assert post["avg_ce"] < pre["avg_ce"], (pre, post)
    assert post["server_ce"] < pre["server_ce"], (pre, post)
    assert np.isfinite(post["avg_ce"])


def test_round_artifacts_finite_and_lora_only_uploaded(protocol_run):
    from repro.core import lora
    _, _, runner = protocol_run
    up = lora.partition(runner.device_params[0], lora.is_lora_leaf)
    assert up and all("_lora_" in k for k in up)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in up.values())


def test_standalone_mode_never_communicates(corpus):
    slm, llm = _bundles()
    fc = FederatedConfig(n_devices=2, rounds=1, local_steps_ccl=1,
                         local_steps_amt=1, server_steps=1, batch_size=8,
                         mode="standalone")
    runner = FederatedRunner(fc, slm, llm, corpus)
    # host copies: the round donates the server trees it replaces
    before = [np.array(x) for x in jax.tree.leaves(runner.server_slm)]
    runner.run_round()
    after = jax.tree.leaves(runner.server_slm)
    for a, b in zip(before, after):
        assert jnp.array_equal(a, b)   # server untouched in standalone


def test_devices_have_heterogeneous_masks(corpus):
    # masks are drawn at construction — no training (and no jit) needed
    slm, llm = _bundles()
    runner = FederatedRunner(
        FederatedConfig(n_devices=3, rounds=1, batch_size=8), slm, llm,
        corpus)
    assert runner.masks.shape == (3, 3)
    assert runner.masks.any(axis=1).all()    # every device has >=1 modality
