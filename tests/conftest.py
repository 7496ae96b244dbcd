import os
import sys

# Smoke tests and benches must see ONE device (the dry-run sets 512 itself,
# in its own process) — keep any user XLA_FLAGS out of the test env.  The
# one exception is the forced host platform device count: the multi-device
# CI job (and the local recipe in docs/architecture.md) runs this suite
# under XLA_FLAGS=--xla_force_host_platform_device_count=8 so the
# mesh-sharded engine paths are exercised on >1 device, and that flag must
# survive into the jax backend init below.
_flags = os.environ.pop("XLA_FLAGS", "")
_keep = [f for f in _flags.split()
         if f.startswith("--xla_force_host_platform_device_count")]
if _keep:
    os.environ["XLA_FLAGS"] = " ".join(_keep)

# property tests import hypothesis at module scope; on a clean container
# without it, install the deterministic shim so collection doesn't crash.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(__file__))
    from _hypothesis_shim import install as _install_hypothesis_shim
    _install_hypothesis_shim()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import use_compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite is dominated by jit time on
# small CPU boxes (a fused federated round is ~40 s of XLA), and the
# compiled artifacts are identical across runs.  First (cold) run pays
# full compile and populates the cache; warm runs load from disk (~4x
# faster suite).  Results are bit-identical either way.  The directory is
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache; CI caches
# it across builds.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from repro.configs.base import ModelConfig  # noqa: E402


@pytest.fixture(scope="session")
def toy_cfg():
    return ModelConfig(
        name="toy", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
        n_modalities=3, modality_dim=32, n_soft_tokens=4, connector_dim=48,
        lora_rank=4, remat=False, activation="gelu")


@pytest.fixture(scope="session")
def rng():
    return jax.random.key(0)


def tree_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating))
