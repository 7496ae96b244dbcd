"""Continuous-batching serving example: a mixed stream of requests with
different prompt lengths and budgets multiplexed through fixed decode slots
over the paged KV cache, then a 2-cohort heterogeneous FederationSpec served
concurrently (one compiled decode per cohort architecture).

  PYTHONPATH=src python examples/serve_continuous.py [--arch qwen3-1.7b]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import ccl as ccl_lib
from repro.core.spec import ClientCohort, FederationSpec
from repro.launch.mesh import use_compile_cache
from repro.launch.serve_engine import CohortServer, EngineConfig, ServingEngine
from repro.models.model import build_model


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    help="assigned arch id (reduced variant is served)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    bundle = build_model(cfg)
    params = ccl_lib.init_unified(jax.random.key(0), bundle)

    econf = EngineConfig(n_slots=args.slots, page_size=16, n_pages=128,
                         max_pages_per_seq=8, max_out=32, buckets=(16, 32))
    engine = ServingEngine(bundle, params, econf)

    rng = np.random.RandomState(0)
    extra = {}
    for i in range(args.requests):
        if cfg.frontend:
            extra["frontend_embeds"] = rng.randn(
                cfg.frontend_tokens, cfg.frontend_dim).astype(np.float32) * 0.3
        engine.submit(rng.randint(0, cfg.vocab_size, (int(rng.randint(4, 30)),)),
                      max_new=int(rng.randint(4, 17)), **extra)

    t0 = time.time()
    done = engine.run()
    wall = time.time() - t0
    n_tok = sum(len(r.out) for r in done.values())
    lats = sorted(r.latency for r in done.values())
    print(f"arch={cfg.name} engine: {len(done)} requests / {args.slots} slots "
          f"in {engine.n_steps} decode steps")
    print(f"  {n_tok} tokens in {wall:.2f}s (incl. compile) — "
          f"p50 latency {lats[len(lats) // 2]:.2f}s, worst {lats[-1]:.2f}s")

    # -- heterogeneous cohorts: two backbone widths served concurrently ----
    wide = cfg
    import dataclasses
    from repro.core.connector import latent_dim
    narrow = dataclasses.replace(cfg, name=cfg.name + "-narrow",
                                 d_model=max(32, cfg.d_model // 2),
                                 d_ff=max(64, cfg.d_ff // 2),
                                 connector_dim=latent_dim(cfg))
    spec = FederationSpec(cohorts=(ClientCohort(model=wide, name="wide"),
                                   ClientCohort(model=narrow, name="narrow")),
                          server_llm=wide)
    server = CohortServer.from_spec(spec, EngineConfig(
        n_slots=2, page_size=16, n_pages=64, max_pages_per_seq=4,
        max_out=16, buckets=(16,)))
    for c in range(2):
        for _ in range(3):
            kw = {}
            if cfg.frontend:
                kw["frontend_embeds"] = rng.randn(
                    cfg.frontend_tokens,
                    cfg.frontend_dim).astype(np.float32) * 0.3
            server.submit(c, rng.randint(0, cfg.vocab_size, (8,)),
                          max_new=6, **kw)
    per_cohort = server.serve()
    for c, (coh, res) in enumerate(zip(spec.cohorts, per_cohort)):
        print(f"  cohort {coh.name} (d_model={coh.model.d_model}): "
              f"{len(res)} requests done, "
              f"{sum(len(r.out) for r in res.values())} tokens")


if __name__ == "__main__":
    main()
