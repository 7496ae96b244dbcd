"""Production mesh construction.

Target: TPU v5e-class pods — 16x16 = 256 chips per pod, 2 pods = 512 chips.
Functions (not module constants) so importing never touches jax device
state; the dry-run launcher sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import os

import jax

_FORCE_FLAG = "--xla_force_host_platform_device_count"

# the checkout root: four directories above src/repro/launch/mesh.py
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def setup_host_env(n_devices: int = 0) -> dict:
    """Python-side mirror of ``launch/env.sh`` (the HomebrewNLP run.sh
    idioms) for everything that CAN still be set after process start.

    - ``TF_CPP_MIN_LOG_LEVEL=4``: mutes XLA/TF C++ log spam (matters for
      benchmark CSV output and CI logs; honored at backend init).
    - ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD``: set only when tcmalloc is
      already preloaded — silences "large alloc" reports for the
      population store's big host buffers.  The LD_PRELOAD itself only
      works at process start; use ``env.sh`` for that.
    - ``n_devices > 0``: forwards to :func:`force_host_device_count`
      (must run before the first jax call).

    Returns the dict of variables it set, for logging.
    """
    changed = {}
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    changed["TF_CPP_MIN_LOG_LEVEL"] = os.environ["TF_CPP_MIN_LOG_LEVEL"]
    preload = os.environ.get("LD_PRELOAD", "")
    if "tcmalloc" in preload:
        os.environ.setdefault(
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000")
        changed["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = (
            os.environ["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"])
    elif any(os.path.exists(c) for c in _TCMALLOC_CANDIDATES):
        # can't LD_PRELOAD from a running process — point at the launcher
        changed["hint"] = ("tcmalloc available but not preloaded; launch "
                           "via src/repro/launch/env.sh to use it")
    if n_devices > 0:
        force_host_device_count(n_devices)
        changed["XLA_FLAGS"] = os.environ["XLA_FLAGS"]
    return changed


def force_host_device_count(n: int) -> None:
    """Make the CPU backend expose ``n`` devices (XLA's forced host
    platform), so the multi-chip sharding paths — ``stacked_client_shardings``
    spreading N federated clients over the "data" axis, the overlap engine's
    dedicated server device — run on a *real* multi-device mesh on any
    laptop/CI box.

    Must be called before jax initializes its backends (i.e. before any
    computation or ``jax.devices()`` call); raises RuntimeError if the
    backend is already up with a different device count.  Equivalent to
    launching under ``XLA_FLAGS=--xla_force_host_platform_device_count=n``.
    """
    prior = os.environ.get("XLA_FLAGS", "")
    flags = [f for f in prior.split() if not f.startswith(_FORCE_FLAG)]
    flags.append(f"{_FORCE_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    got = jax.local_device_count()   # initializes the backend if not yet up
    if got != n:
        raise RuntimeError(
            f"jax backend already initialized with {got} devices; set "
            f"XLA_FLAGS={_FORCE_FLAG}={n} in the environment before the "
            "first jax call instead")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``) — a fixed path,
    never a temporary name, a pid or a time, because a later process finds
    an entry only under the directory that wrote it.  Call it once, before
    the first compile, from every entry point (scripts, benchmarks, the
    test ``conftest.py``).

    An entry's key includes the ops' metadata: by default JAX strips it,
    and a program that differs from a cached one only in its named scopes
    (which a profiler trace attributes device time by) would load the
    other's executable and show the other's names.  Locations keep only
    the innermost source frame (with the full scope path), so the key does
    not depend on the caller.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12         # FLOP/s
HBM_BW = 819e9                   # B/s
ICI_BW = 50e9                    # B/s per link


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the compiler propagates shardings
    from the placed arguments.  ``jax.make_mesh`` otherwise makes
    ``Explicit`` axes, which put shardings into the traced types — and the
    engines' unsharded intermediates (``jnp.ones`` masks concatenated with
    client-sharded data) then fail to trace on more than one device."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs of the same SPMD code."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_federated_mesh(n_model: int = 1):
    """Mesh for the vectorized federated engine: every local device joins
    the "data" axis, which the sharding rules alias to the stacked "device"
    (client) axis — N clients parallelize across chips.  On a single-device
    host this degenerates to the (1, 1) host mesh, so the engine stays
    exact there."""
    n_data = max(1, len(jax.devices()) // max(1, n_model))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_cohort_meshes(n_cohorts: int, n_model: int = 1):
    """Disjoint per-cohort meshes for heterogeneous federations (the
    overlap engine's ``mesh=[...]`` form).

    Differently-shaped cohorts cannot share one ``vmap`` trace, so placing
    each cohort on its own device slice lets their device phases execute
    *concurrently* via async dispatch instead of serializing on one chip
    set.  The local devices are split evenly, leading cohorts taking the
    remainder; each slice becomes a ("data", "model") mesh whose "data"
    axis carries that cohort's stacked clients (``n_model`` is clamped to
    the slice size, and a slice that is not a multiple of ``n_model``
    drops its tail devices — mesh shapes must be rectangular).  With fewer
    devices than cohorts the surplus cohorts share the last device
    (degenerate (1, 1) meshes) — still correct, no cohort parallelism.
    """
    import numpy as np
    devs = jax.devices()
    base, rem = divmod(len(devs), n_cohorts)
    meshes, lo = [], 0
    for c in range(n_cohorts):
        take = base + (1 if c < rem else 0)
        if take == 0:               # more cohorts than devices
            sl = [devs[-1]]
        else:
            sl = devs[lo:lo + take]
            lo += take
        nm = max(1, min(n_model, len(sl)))
        n_data = len(sl) // nm
        arr = np.array(sl[:n_data * nm]).reshape(n_data, nm)
        meshes.append(jax.sharding.Mesh(arr, ("data", "model")))
    return meshes


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_chips(mesh) -> int:
    return mesh.devices.size
