"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's math but not the TPU's lowering rules (block
tiling, Mosaic layouts, VMEM limits); these tests hand the real compiler
each kernel at the shapes the system runs — the paper SLM's decode step
and stacked LoRA upload — on a ``v5e:2x2`` topology that is described, not
attached.  Nothing executes, so they cost no chip and check no values (the
interpret-mode parity tests in ``test_kernels.py`` / ``test_channel.py``
pin those).  The topology is described inside a fixture: the TPU library
is loaded by the one test process that runs this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import ccl, lora
from repro.core.channel import ChannelSpec
from repro.kernels import ops
from repro.models.model import build_model


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: a TPU program written to it could not be read back without a
    chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *args):
    """Compile ``fn`` for the described chip; its optimized HLO text."""
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return jax.jit(fn).lower(*abstract).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("B,H,K,D", [
    (8, 20, 20, 64),       # mlecs-slm-720m decode: 20 heads of 64, no GQA
    (8, 16, 8, 128),       # a GQA layout: two query heads per kv head
])
def test_paged_attention_compiles(one_chip, B, H, K, D):
    ps, M, P = 16, 16, 128
    text = _compiled_text(
        lambda q, k, v, bt, ln, w: ops.paged_attention(q, k, v, bt, ln, w),
        one_chip,
        _sds((B, 1, H, D), jnp.bfloat16), _sds((P, ps, K, D), jnp.bfloat16),
        _sds((P, ps, K, D), jnp.bfloat16), _sds((B, M), jnp.int32),
        _sds((B,), jnp.int32), _sds((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("qmax", [127, 7])           # int8, int4 codes
@pytest.mark.parametrize("R", [127, 128, 3072])      # prime, one block, many
def test_quantize_compiles(one_chip, R, qmax):
    text = _compiled_text(lambda x: ops.quantize(x, qmax=qmax), one_chip,
                          _sds((R, 128), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("R", [127, 128, 3072])
def test_dequantize_compiles(one_chip, R):
    text = _compiled_text(ops.dequantize, one_chip,
                          _sds((R, 128), jnp.int8), _sds((R,), jnp.float32))
    assert "tpu_custom_call" in text


def test_channel_encode_compiles_for_slm_upload(one_chip):
    """The int8 error-feedback uplink of two stacked mlecs-slm-720m clients:
    every LoRA leaf of the published-width model, flattened into
    thousands of 128-wide tile rows."""
    cfg = get_config("mlecs-slm-720m")
    params = jax.eval_shape(
        lambda k: ccl.init_unified(k, build_model(cfg)), jax.random.key(0))
    upload = {k: _sds((2,) + v.shape, jnp.float32) for k, v in
              lora.partition(params, lora.is_lora_leaf).items()}
    chan = ChannelSpec(codec="int8", error_feedback=True).make()
    rows = sum(2 * chan._tiles(int(np.prod(v.shape[1:])))
               for v in upload.values())
    assert rows > 1024
    text = _compiled_text(chan.encode, one_chip, upload,
                          chan.init_state(upload))
    assert "tpu_custom_call" in text
