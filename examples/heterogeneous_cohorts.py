"""Model-structure heterogeneity end-to-end: a two-cohort FederationSpec
with different SLM backbones (d_model 48 vs 64) AND disjoint modality
subsets, run through the vectorized engine.

  PYTHONPATH=src python examples/heterogeneous_cohorts.py

Each cohort keeps its own device-stacked state (intra-cohort homogeneity is
what makes a cohort vmap-able); across cohorts the protocol exchanges only
the *shared-shape* LoRA subset with the server SLM — cohort-specific
adapters federate within their cohort.  With more than one local device
(the CI smoke job forces 2 host devices) the cohort stacks additionally
shard over the mesh "data" axis.
"""
import os

# demonstrate the multi-device path on any laptop: force 2 host devices
# unless the environment already configured the XLA platform
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

import jax  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.federated import FederatedRunner  # noqa: E402
from repro.core.spec import ClientCohort, FederationSpec  # noqa: E402
from repro.data.synthetic import synthetic_multimodal_corpus  # noqa: E402
from repro.launch.mesh import make_federated_mesh, use_compile_cache  # noqa: E402

use_compile_cache()

KW = dict(n_modalities=3, modality_dim=32, n_soft_tokens=4, connector_dim=48,
          lora_rank=4, remat=False, activation="gelu", vocab_size=128)
slm_small = ModelConfig(name="edge-small", family="dense", n_layers=2,
                        d_model=48, n_heads=4, n_kv_heads=2, head_dim=12,
                        d_ff=96, **KW)
slm_wide = ModelConfig(name="edge-wide", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, head_dim=12,
                       d_ff=128, **KW)
llm = ModelConfig(name="cloud-llm", family="dense", n_layers=2, d_model=96,
                  n_heads=4, n_kv_heads=2, head_dim=24, d_ff=192, **KW)

spec = FederationSpec(
    cohorts=(
        # vision+audio edge domain: small backbone, modalities {0, 1}
        ClientCohort(model=slm_small, n_clients=2, name="av-edge",
                     modalities=(0, 1)),
        # sensor edge domain: wider backbone, modality {2} only, denser MER
        ClientCohort(model=slm_wide, n_clients=2, name="sensor-edge",
                     modalities=(2,), rho=0.9),
    ),
    server_llm=llm,
    rounds=2, local_steps_ccl=2, local_steps_amt=2, server_steps=2,
    batch_size=8, lr=1e-2, rho=0.7, seed=0, engine="vectorized")

corpus = synthetic_multimodal_corpus(0, 384, 24, 128, n_classes=4,
                                     n_modalities=3, modality_dim=32,
                                     template_len=4)
mesh = make_federated_mesh() if jax.device_count() > 1 else None
runner = FederatedRunner(spec, corpus, mesh=mesh)

print(f"devices={jax.device_count()}  cohorts="
      + ", ".join(f"{c.name}(n={c.n_clients}, d={c.model.d_model}, "
                  f"M={c.modalities})" for c in spec.cohorts))
for rt in runner.cohorts:
    print(f"  {rt.spec.name}: {len(rt.shared)} LoRA keys shared with the "
          f"server, {len(rt.own)} cohort-local")

summaries = []
for rnd in range(spec.rounds):
    out = runner.run_round()
    s = out["summary"]
    summaries.append(s)
    print(f"round {rnd}: avg_acc={s['avg_acc']:.3f} "
          f"avg_ce={s['avg_ce']:.3f} server_ce={s['server_ce']:.3f}")
    for c, coh in enumerate(spec.cohorts):
        off = spec.offsets[c]
        cs = out["client"][off:off + coh.n_clients]
        accs = ", ".join(f"{x['acc']:.3f}" for x in cs)
        print(f"  {coh.name}: client acc [{accs}]")

assert summaries[-1]["avg_ce"] < summaries[0]["avg_ce"], \
    "heterogeneous federation failed to improve"
print("OK: heterogeneous cohorts trained, aggregated on the shared "
      "subset, and improved")
