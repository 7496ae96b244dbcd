"""Shared benchmark scaffolding: the scaled-down SLM/LLM pair (the paper's
MiniLLM-gpt2-720M / GPT-J-6B roles at laptop scale), the synthetic VAST /
UR-FALL analogues, and the heterogeneous-cohort spec builders for the
model-structure-heterogeneity sweeps."""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.federated import FederatedConfig, FederatedRunner
from repro.core.spec import ClientCohort, FederationSpec
from repro.data.synthetic import synthetic_multimodal_corpus
from repro.launch.mesh import use_compile_cache
from repro.models.model import build_model

RESULTS_DIR = os.path.join("experiments", "results")

# every benchmark process imports this module: one compile cache for all
use_compile_cache()

_COMMON = dict(n_modalities=3, modality_dim=32, n_soft_tokens=4,
               connector_dim=48, remat=False, activation="gelu",
               vocab_size=128)


def slm_cfg(lora_rank: int = 4) -> ModelConfig:
    return ModelConfig(name="bench-slm", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                       d_ff=128, lora_rank=lora_rank, **_COMMON)


def llm_cfg() -> ModelConfig:
    return ModelConfig(name="bench-llm", family="dense", n_layers=3,
                       d_model=96, n_heads=4, n_kv_heads=2, head_dim=24,
                       d_ff=192, lora_rank=4, **_COMMON)


def vast_corpus(seed: int = 0, n: int = 512):
    """Summary-generation analogue (VAST): 8-token class templates."""
    return synthetic_multimodal_corpus(seed, n, 32, 128, n_classes=6,
                                       n_modalities=3, modality_dim=32,
                                       template_len=8)


def urfall_corpus(seed: int = 0, n: int = 512):
    """3-class classification analogue (UR-FALL): 1-token label."""
    return synthetic_multimodal_corpus(seed, n, 24, 128, n_classes=3,
                                       n_modalities=3, modality_dim=32,
                                       template_len=1)


METHOD_CONFIGS = {
    # method -> (FederatedConfig overrides, slm lora_rank)
    "standalone": (dict(mode="standalone"), 4),
    "multi-fedavg": (dict(mode="fedavg", use_ccl=False), 4),
    "fedmllm": (dict(mode="fedavg", use_ccl=False, prox_weight=0.01), 4),
    "fedilora": (dict(mode="fedavg", use_ccl=False), 12),   # r=24 vs r=8 paper-scaled
    "co-plms": (dict(mode="mlecs", use_ccl=False, use_mma=False,
                     use_seccl=True), 4),
    "ml-ecs": (dict(mode="mlecs"), 4),
}


def make_runner(method: str, corpus, rho: float, rounds: int = 3,
                n_devices: int = 3, seed: int = 0, mesh=None, **extra
                ) -> FederatedRunner:
    overrides, rank = METHOD_CONFIGS[method]
    fc = FederatedConfig(n_devices=n_devices, rounds=rounds,
                         local_steps_ccl=2, local_steps_amt=2,
                         server_steps=2, batch_size=8, lr=1e-2, rho=rho,
                         seed=seed, **{**overrides, **extra})
    return FederatedRunner(fc, build_model(slm_cfg(rank)),
                           build_model(llm_cfg()), corpus, mesh=mesh)


def run_method(method: str, corpus, rho: float, rounds: int = 3,
               n_devices: int = 3, seed: int = 0, **extra):
    runner = make_runner(method, corpus, rho, rounds=rounds,
                         n_devices=n_devices, seed=seed, **extra)
    hist = runner.run()
    return hist[-1]["summary"], hist


# distinct backbone widths for the architecture-heterogeneity sweep; every
# variant keeps the bench head layout (4 x 16) so the LoRA B matrices stay
# shape-shared with the server SLM while the A matrices go cohort-local
_COHORT_D_MODELS = (64, 48, 32, 80)


def heterogeneous_spec(n_cohorts: int, total_clients: int = 4,
                       rho: float = 0.7, rounds: int = 2, seed: int = 0,
                       engine: str = "vectorized", **extra
                       ) -> FederationSpec:
    """``n_cohorts`` distinct SLM architectures at a FIXED total client
    count — the Table-1 heterogeneity sweep's unit.  ``n_cohorts=1`` is the
    homogeneous baseline (bit-for-bit the legacy bench runner's topology);
    larger counts split the same N clients across progressively more
    backbone widths, leading cohorts absorbing the remainder."""
    assert 1 <= n_cohorts <= len(_COHORT_D_MODELS)
    assert total_clients >= n_cohorts
    base, rem = divmod(total_clients, n_cohorts)
    cohorts = []
    for c in range(n_cohorts):
        d = _COHORT_D_MODELS[c]
        model = dataclasses.replace(slm_cfg(), name=f"bench-slm-d{d}",
                                    d_model=d, d_ff=2 * d)
        cohorts.append(ClientCohort(
            model=model, n_clients=base + (1 if c < rem else 0),
            name=f"d{d}"))
    return FederationSpec(cohorts=tuple(cohorts), server_llm=llm_cfg(),
                          rounds=rounds, local_steps_ccl=2,
                          local_steps_amt=2, server_steps=2, batch_size=8,
                          lr=1e-2, rho=rho, seed=seed, engine=engine,
                          **extra)


def cohort_summaries(round_metrics: dict, spec: FederationSpec) -> dict:
    """Slice one round's global client-metric list into per-cohort rows
    (avg/best/worst acc + avg ce), keyed by cohort name."""
    out = {}
    for c, (coh, off) in enumerate(zip(spec.cohorts, spec.offsets)):
        cs = round_metrics["client"][off:off + coh.n_clients]
        out[coh.name or f"cohort{c}"] = {
            "n_clients": coh.n_clients,
            "d_model": coh.model.d_model,
            "avg_acc": float(np.mean([x["acc"] for x in cs])),
            "best_acc": float(np.max([x["acc"] for x in cs])),
            "worst_acc": float(np.min([x["acc"] for x in cs])),
            "avg_ce": float(np.mean([x["ce"] for x in cs])),
        }
    return out


def time_phases(runner: FederatedRunner, n_rounds: int = 3) -> dict:
    """Per-phase wall-clock of a communication round: ``train`` (the fused
    or looped round itself, ``evaluate=False`` + sync), ``eval`` (all N
    client evals), and ``server`` (the N-independent SE-CCL public-test
    eval).  The warmup rounds incl. eval (jit compilation) are reported as
    ``compile_s``; metric results sync to host floats, so each phase timer
    measures completed work, not enqueue.  For the overlap engine,
    ``sync()`` blocks on the device critical path only — the pipelined
    server phase is (by design) off it.  Warmup runs ``staleness + 2``
    rounds: the first compiles the round function(s), the next cover the
    recompiles triggered when input shardings change after round 1 / the
    first redistribution (on a mesh the round-1 output placement differs
    from the initial one) — without them a fresh XLA compile lands inside
    the first TIMED round and poisons every mean."""
    with Timer() as t0:
        for _ in range(2 + getattr(runner.cfg, "staleness", 0)):
            runner.run_round(evaluate=False)
            runner.sync()
        runner.drain()
        runner.evaluate_clients()
        runner.evaluate_server()
    train, ev, srv = [], [], []
    for _ in range(n_rounds):
        with Timer() as t:
            runner.run_round(evaluate=False)
            runner.sync()
        train.append(t.s)
        with Timer() as t:
            runner.evaluate_clients()
        ev.append(t.s)
        with Timer() as t:
            runner.evaluate_server()
        srv.append(t.s)
    return {"compile_s": t0.s,
            "train_s": train, "mean_train_s": float(np.mean(train)),
            "eval_s": ev, "mean_eval_s": float(np.mean(ev)),
            "server_eval_s": srv,
            "mean_server_eval_s": float(np.mean(srv)),
            # aliases: the train phase IS the old whole-round timing, so
            # earlier-schema JSON consumers keep working
            "round_s": train, "mean_round_s": float(np.mean(train))}


def save_result(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0
