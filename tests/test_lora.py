"""LoRA partition/combine/merge + the communication-fraction claim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core import ccl as ccl_lib
from repro.core import lora
from repro.models.model import build_model

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")


@pytest.fixture(scope="module")
def setup(request):
    cfg = get_config("qwen3-1.7b").reduced()
    bundle = build_model(cfg)
    params = ccl_lib.init_unified(jax.random.key(0), bundle)
    return cfg, bundle, params


def test_partition_combine_roundtrip(setup):
    _, _, params = setup
    train = lora.partition(params)
    rebuilt = lora.combine(params, train)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        assert jnp.array_equal(a, b)


def test_partition_selects_only_lora_and_connector(setup):
    _, _, params = setup
    train = lora.partition(params)
    assert train, "trainable set empty"
    for k in train:
        assert lora.default_trainable(k), k
        assert ("_lora_" in k) or k.startswith(("connector", "frontend")), k


def test_combine_with_modified_leaves_changes_only_those(setup):
    _, _, params = setup
    train = lora.partition(params)
    k0 = sorted(train)[0]
    train2 = dict(train)
    train2[k0] = train2[k0] + 1.0
    rebuilt = lora.combine(params, train2)
    flat_new = lora.partition(rebuilt, lambda p: True)
    flat_old = lora.partition(params, lambda p: True)
    for k in flat_old:
        same = jnp.array_equal(flat_old[k], flat_new[k])
        assert same == (k != k0), k


def test_merge_lora_forward_equivalence(setup):
    """Forward with adapters == forward after W' = W + (α/r)BA merge —
    the paper's Eq. 1 consistency, and what serving relies on."""
    cfg, bundle, params = setup
    # give the (zero-init) B matrices real values so the test is non-trivial
    train = lora.partition(params, lora.is_lora_leaf)
    keys = jax.random.split(jax.random.key(1), len(train))
    train = {k: 0.02 * jax.random.normal(kk, v.shape, v.dtype)
             for kk, (k, v) in zip(keys, sorted(train.items()))}
    params = lora.combine(params, train)

    toks = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    logits_adapter, _ = bundle.logits(params, batch)

    merged = lora.merge_lora(params, cfg)
    # the adapters are gone from the merged tree: their effect is now in W,
    # and keeping them would apply BA a second time
    assert lora.partition(merged, lora.is_lora_leaf) == {}
    logits_merged, _ = bundle.logits(merged, batch)
    np.testing.assert_allclose(np.asarray(logits_adapter, np.float32),
                               np.asarray(logits_merged, np.float32),
                               atol=0.12, rtol=0.05)  # bf16 weight rounding


def test_communicated_fraction_matches_paper_slm():
    """Paper Fig. 3: LoRA r=8 on the 720M SLM communicates <1% of params
    (paper reports 0.65% including fused representations)."""
    cfg = get_config("mlecs-slm-720m")
    frac = cfg.n_lora_params() / cfg.n_params()
    assert 0.001 < frac < 0.012, frac


@given(st.integers(0, 1000))
def test_fraction_consistency_analytic_vs_tree(seed):
    """Analytic n_lora_params matches the actual parameter tree count."""
    cfg = get_config("qwen3-1.7b").reduced()
    bundle = build_model(cfg)
    params = jax.eval_shape(lambda: bundle.init(jax.random.key(0)))
    tree_count = lora.n_params(lora.partition(params, lora.is_lora_leaf))
    assert tree_count == cfg.n_lora_params(), (tree_count,
                                               cfg.n_lora_params())
