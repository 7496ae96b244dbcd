"""Quickstart: the ML-ECS pieces in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py

Builds a small unified model (connector + LoRA'd backbone), runs one CCL
step with a server anchor, one AMT step on private data, aggregates two
simulated device uploads with MMA, and prints the communicated fraction.
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import ccl as ccl_lib
from repro.core import lora, mma
from repro.data.pipeline import batches
from repro.data.synthetic import synthetic_multimodal_corpus
from repro.launch.mesh import use_compile_cache
from repro.models.model import build_model
from repro.optim.adamw import adamw

use_compile_cache()

cfg = ModelConfig(name="quickstart", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=128, n_modalities=3, modality_dim=32,
                  n_soft_tokens=4, connector_dim=48, lora_rank=4,
                  remat=False, activation="gelu")
bundle = build_model(cfg)
params = ccl_lib.init_unified(jax.random.key(0), bundle)

corpus = synthetic_multimodal_corpus(0, 256, 32, 128, n_classes=4,
                                     n_modalities=3, modality_dim=32)
it = batches(corpus, batch_size=8, seed=0)
opt = adamw(3e-3)
opt_state = opt.init(lora.partition(params))

# --- CCL (Eq. 11): align modality reps against a server-provided anchor ---
ccl_step = ccl_lib.make_local_step(bundle, opt, ccl_weight=0.5)
batch = next(it)
anchor = jax.random.normal(jax.random.key(1), (8, cfg.connector_dim))
params, opt_state, m = ccl_step(params, opt_state, batch, anchor)
print("CCL step:", {k: round(float(v), 4) for k, v in m.items()})

# --- AMT (Eq. 12): LoRA-only tuning on private data ---
amt_step = ccl_lib.make_local_step(bundle, opt, ccl_weight=0.0,
                                   with_anchor=False)
params, opt_state, m = amt_step(params, opt_state, next(it))
print("AMT step:", {k: round(float(v), 4) for k, v in m.items()})

# --- MMA (Eq. 13): modality-aware aggregation of two device uploads ---
up1 = lora.partition(params, lora.is_lora_leaf)
up2 = {k: v * 0.5 for k, v in up1.items()}
agg = mma.aggregate([up1, up2], mma.aggregation_weights([3, 1]))
print("MMA weights for |M|=[3,1]:",
      [round(float(w), 3) for w in mma.aggregation_weights([3, 1])])

# --- the communication claim ---
frac = lora.communicated_fraction(params)
print(f"communicated fraction (LoRA only): {100 * frac:.3f}% of parameters")
