"""Serving-engine tests: paged cache contract per family, the continuous-
batching scheduler vs the seed ``generate()`` loop, cache re-seating, and
heterogeneous cohort serving."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.spec import ClientCohort, FederationSpec
from repro.launch.serve import _reseat_cache, generate
from repro.launch.serve_engine import CohortServer, EngineConfig, ServingEngine
from repro.models.model import build_model
from repro.models.paged import pages_for

FAMS = {
    "dense": dict(family="dense"),
    "dense_swa": dict(family="dense", sliding_window=8),
    "moe": dict(family="moe", n_experts=4, top_k=2, d_ff_expert=64,
                capacity_factor=4.0),
    "ssm": dict(family="ssm", ssm_state=8, ssm_head_dim=16, ssm_chunk=8),
    "hybrid": dict(family="hybrid", ssm_state=8, ssm_head_dim=16,
                   ssm_chunk=8, lora_targets=("wq", "wo", "in_proj")),
    "encdec": dict(family="encdec", n_enc_layers=2, frontend="audio",
                   frontend_tokens=16, frontend_dim=24, activation="gelu"),
}


def _cfg(**kw):
    base = dict(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=8, d_ff=64, vocab_size=64, n_modalities=0,
                remat=False, lora_rank=2, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _batch(cfg, toks, key=7):
    batch = {"tokens": toks}
    if cfg.frontend:
        batch["frontend_embeds"] = jax.random.normal(
            jax.random.key(key), (toks.shape[0], cfg.frontend_tokens,
                                  cfg.frontend_dim), jnp.float32) * 0.5
    return batch


# ---------------------------------------------------------------------------
# paged cache contract: prefill -> insert -> K decode steps == full forward

@pytest.mark.parametrize("use_kernel", [None, True])   # jnp twin, kernel
@pytest.mark.parametrize("fam", list(FAMS))
def test_paged_decode_matches_forward(fam, use_kernel):
    """Prefill, insert and decode through the paged pool reproduce the
    full forward, with the jnp twin and with the Pallas kernel (interpret
    mode on the CPU) reading the pool."""
    cfg = _cfg(**FAMS[fam])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    S, K, ps = 8, 4, 4
    # attention families may prefill right-padded to a compile bucket;
    # recurrent state would fold padding in, so ssm/hybrid use exact length
    pad = 0 if fam in ("ssm", "hybrid") else 4
    toks = jax.random.randint(jax.random.key(1), (1, S + K), 0,
                              cfg.vocab_size)
    full_logits, _ = b.logits(params, _batch(cfg, toks))
    P = full_logits.shape[1] - (S + K)

    pstate = b.init_paged(n_slots=2, n_pages=16, page_size=ps)
    pre = jnp.pad(toks[:, :S], ((0, 0), (0, pad)))
    last, pack, kv_len = b.prefill_paged(
        params, _batch(cfg, pre), jnp.int32(S))
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(full_logits[:, P + S - 1],
                                          np.float32),
                               atol=2e-3, rtol=2e-3)

    slot = 1                            # exercise a non-zero slot
    n_pg = pages_for(P + S + pad + K, ps)
    page_ids = jnp.arange(1, 1 + n_pg, dtype=jnp.int32)  # page 0 = scratch
    pstate = b.insert_paged(pstate, pack, jnp.int32(slot), page_ids)
    bt = jnp.zeros((2, 8), jnp.int32).at[slot, :n_pg].set(page_ids)
    seq_lens = jnp.zeros((2,), jnp.int32).at[slot].set(kv_len)
    active = jnp.zeros((2,), bool).at[slot].set(True)

    for i in range(K):
        tok = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(toks[0, S + i])
        logits, pstate = b.decode_paged(params, pstate, bt, seq_lens, tok,
                                        active, use_kernel)
        np.testing.assert_allclose(
            np.asarray(logits[slot], np.float32),
            np.asarray(full_logits[0, P + S + i], np.float32),
            atol=6e-2, rtol=5e-2, err_msg=f"step {i}")
        seq_lens = seq_lens + active


# ---------------------------------------------------------------------------
# engine vs seed generate(): greedy outputs must be identical

def test_engine_matches_generate_greedy():
    cfg = _cfg(**FAMS["dense"])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    econf = EngineConfig(n_slots=2, page_size=4, n_pages=32,
                         max_pages_per_seq=8, max_out=16, buckets=(8, 16))
    engine = ServingEngine(b, params, econf)

    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32),
             int(m)) for n, m in [(5, 6), (8, 3), (12, 9), (3, 1),
                                  (9, 12), (6, 4)]]
    rids = [engine.submit(t, max_new=m) for t, m in reqs]
    done = engine.run()
    assert sorted(done) == sorted(rids)

    for rid, (toks, m) in zip(rids, reqs):
        want = generate(b, params, jnp.asarray(toks)[None], max_new=m)
        got = done[rid].out
        assert got.tolist() == np.asarray(want[0]).tolist(), \
            f"req {rid} (len {len(toks)}, budget {m})"

    # eviction returned every page and slot to the free lists
    assert len(engine._free_pages) == econf.n_pages - 1
    assert sorted(engine._free_slots) == [0, 1]


def test_engine_donates_page_pool():
    """The insert and the decode step take the pool donated: the buffers a
    call starts from are deleted once it has run (the pool is written in
    place), and greedy outputs still match ``generate()``."""
    cfg = _cfg(**FAMS["dense"])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    econf = EngineConfig(n_slots=2, page_size=4, n_pages=32,
                         max_pages_per_seq=8, max_out=16, buckets=(8, 16))
    engine = ServingEngine(b, params, econf)
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32),
             int(m)) for n, m in [(5, 6), (11, 4), (7, 9)]]
    rids = [engine.submit(t, max_new=m) for t, m in reqs]

    def pools():
        return engine.pstate["k_pages"], engine.pstate["v_pages"]

    before = pools()
    engine._try_admit()                           # inserts only
    assert all(x.is_deleted() for x in before)
    while engine.busy:
        before = pools()
        engine.tick()                             # a decode step each tick
        assert all(x.is_deleted() for x in before)
        assert not any(x.is_deleted() for x in pools())
    for rid, (toks, m) in zip(rids, reqs):
        want = generate(b, params, jnp.asarray(toks)[None], max_new=m)
        assert engine.finished[rid].out.tolist() == \
            np.asarray(want[0]).tolist(), f"req {rid}"


def test_engine_eos_and_budget_clamp():
    cfg = _cfg(**FAMS["dense"])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    econf = EngineConfig(n_slots=2, page_size=4, n_pages=16,
                         max_pages_per_seq=4, max_out=4, buckets=(8,))
    engine = ServingEngine(b, params, econf)
    toks = np.arange(5, dtype=np.int32)
    r_long = engine.submit(toks, max_new=99)      # clamped to max_out
    r_one = engine.submit(toks, max_new=1)        # finishes at admission
    done = engine.run()
    assert len(done[r_long].out) == econf.max_out
    assert len(done[r_one].out) == 1
    # eos: pick whatever greedy emits first and declare it terminal
    eos = int(done[r_one].out[0])
    engine2 = ServingEngine(b, params, dataclasses.replace(econf, eos_id=eos))
    r = engine2.submit(toks, max_new=99)
    done2 = engine2.run()
    assert len(done2[r].out) == 1 and int(done2[r].out[0]) == eos


def test_engine_page_pool_exhaustion_mid_flight():
    """A request that fits the block table but NOT the current free pool
    must wait — even with a slot free — and be admitted the tick after an
    eviction returns its pages, with greedy output unaffected."""
    cfg = _cfg(**FAMS["dense"])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    # 5 usable pages (page 0 = scratch); each request needs 4, so the
    # second queues on pages despite the second slot being free
    econf = EngineConfig(n_slots=2, page_size=4, n_pages=6,
                         max_pages_per_seq=4, max_out=8, buckets=(8,))
    engine = ServingEngine(b, params, econf)
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32), 6)
            for _ in range(2)]
    rids = [engine.submit(t, max_new=m) for t, m in reqs]

    engine.tick()
    assert len(engine._slot_req) == 1 and len(engine.pending) == 1
    assert len(engine._free_slots) == 1          # blocked on pages, not slots
    while engine.pending:                        # first eviction unblocks it
        assert len(engine._slot_req) <= 1
        engine.tick()
    assert rids[0] in engine.finished            # admission followed eviction
    done = engine.run()
    assert sorted(done) == sorted(rids)
    for rid, (toks, m) in zip(rids, reqs):
        want = generate(b, params, jnp.asarray(toks)[None], max_new=m)
        assert done[rid].out.tolist() == np.asarray(want[0]).tolist()
    # every page and slot returned to the free lists
    assert sorted(engine._free_pages) == list(range(1, econf.n_pages))
    assert sorted(engine._free_slots) == [0, 1]


def test_engine_admission_overflow_raises():
    cfg = _cfg(**FAMS["dense"])
    b = build_model(cfg)
    params = b.init(jax.random.key(0))
    econf = EngineConfig(n_slots=1, page_size=4, n_pages=16,
                         max_pages_per_seq=2, max_out=4, buckets=(8,))
    engine = ServingEngine(b, params, econf)
    engine.submit(np.zeros(7, np.int32), max_new=4)      # 8+4 > 2*4
    with pytest.raises(ValueError, match="block-table"):
        engine.run()                 # admission happens at tick time


# ---------------------------------------------------------------------------
# heterogeneous cohorts: one engine per architecture, served concurrently

def test_cohort_server_heterogeneous():
    wide = _cfg(**FAMS["dense"])
    narrow = dataclasses.replace(wide, name="t-narrow", d_model=16, d_ff=32)
    spec = FederationSpec(cohorts=(ClientCohort(model=wide, name="wide"),
                                   ClientCohort(model=narrow, name="narrow")),
                          server_llm=wide)
    econf = EngineConfig(n_slots=2, page_size=4, n_pages=16,
                         max_pages_per_seq=4, max_out=8, buckets=(8,))
    server = CohortServer.from_spec(spec, econf)
    rng = np.random.RandomState(0)
    reqs = {c: [(rng.randint(0, wide.vocab_size, (6,)).astype(np.int32), 5)
                for _ in range(2)] for c in range(2)}
    rids = {c: [server.submit(c, t, max_new=m) for t, m in reqs[c]]
            for c in range(2)}
    per_cohort = server.serve()
    for c in range(2):
        bundle = server.engines[c].bundle
        params = server.engines[c].params
        for rid, (toks, m) in zip(rids[c], reqs[c]):
            want = generate(bundle, params, jnp.asarray(toks)[None],
                            max_new=m, merge=False)   # engine pre-merged
            got = per_cohort[c][rid].out
            assert got.tolist() == np.asarray(want[0]).tolist(), \
                f"cohort {c} req {rid}"
    # distinct architectures actually served (not one shared engine)
    assert server.engines[0].bundle.cfg.d_model != \
        server.engines[1].bundle.cfg.d_model


# ---------------------------------------------------------------------------
# _reseat_cache routing

def test_reseat_routes_kv_and_pos():
    big = {"k": jnp.zeros((2, 1, 12, 2, 8)), "v": jnp.zeros((2, 1, 12, 2, 8)),
           "pos": jnp.zeros((2, 1), jnp.int32)}
    small = {"k": jnp.ones((2, 1, 8, 2, 8)), "v": jnp.ones((2, 1, 8, 2, 8)),
             "pos": jnp.full((2, 1), 8, jnp.int32)}
    out = _reseat_cache(big, small)
    assert out["k"].shape == big["k"].shape
    np.testing.assert_array_equal(np.asarray(out["k"][:, :, :8]), 1.0)
    np.testing.assert_array_equal(np.asarray(out["k"][:, :, 8:]), 0.0)
    assert int(out["pos"][0, 0]) == 8


def test_reseat_state_shape_mismatch_raises():
    big = {"ssm_h": jnp.zeros((2, 1, 4, 16, 8))}
    small = {"ssm_h": jnp.zeros((2, 1, 4, 16, 4))}
    with pytest.raises(ValueError, match="match exactly"):
        _reseat_cache(big, small)


def test_reseat_unknown_leaf_raises():
    with pytest.raises(KeyError):
        _reseat_cache({"k": jnp.zeros((1, 1, 4, 1, 4)),
                       "mystery": jnp.zeros(3)},
                      {"mystery": jnp.zeros(3)})
    with pytest.raises(KeyError):   # leaf absent from the serving cache
        _reseat_cache({"k": jnp.zeros((1, 1, 4, 1, 4))},
                      {"ssm_h": jnp.zeros(3)})
