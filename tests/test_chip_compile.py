"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's math but not the TPU's lowering rules (block
tiling, Mosaic layouts, VMEM limits); these tests hand the real compiler
each kernel at the shapes the system runs — the paper SLM's decode step
and stacked LoRA upload — on a ``v5e:2x2`` topology that is described, not
attached.  The serving engine's jitted step and insert are compiled whole
too, and their optimized HLO is read for what would move the page pool.  Nothing executes, so they cost no chip and check no values (the
interpret-mode parity tests in ``test_kernels.py`` / ``test_channel.py``
pin those).  The topology is described inside a fixture: the TPU library
is loaded by the one test process that runs this file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import ccl, lora
from repro.core.channel import ChannelSpec
from repro.kernels import ops
from repro.launch.serve_engine import (EngineConfig, init_sched, jit_insert,
                                       jit_step)
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
    """Compile ``fn`` (jitted here unless it already is) for the described
    chip; its optimized HLO text."""
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    return fn.lower(*abstract).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("B,H,K,D,dtype", [
    (8, 20, 20, 64, jnp.bfloat16),  # mlecs-slm-720m decode: 20 heads of 64
    (8, 16, 8, 128, jnp.bfloat16),  # a GQA layout: two query heads per kv head
    (4, 4, 2, 16, jnp.float32),     # f32 operands: the HIGHEST contractions
])
def test_paged_attention_compiles(one_chip, B, H, K, D, dtype):
    ps, M, P, L = 16, 16, 128, 3
    text = _compiled_text(
        lambda q, k, v, lyr, bt, ln, w: ops.paged_attention(
            q, k, v, lyr, bt, ln, w),
        one_chip,
        _sds((B, 1, H, D), dtype), _sds((L, P, ps, K * D), dtype),
        _sds((L, P, ps, K * D), dtype), _sds((), jnp.int32),
        _sds((B, M), jnp.int32), _sds((B,), jnp.int32),
        _sds((), jnp.int32))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the serving engine's programs keep the page pool in place

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def _largest_array(shape: str) -> int:
    """Bytes of the largest array in an HLO result shape (tuples too)."""
    return max((_BYTES.get(t, 4) * math.prod(int(d) for d in dims.split(",")
                                             if d)
                for t, dims in _ARRAY.findall(shape)), default=0)


def _hlo_instructions(text: str):
    """(computation, opcode, result shape, called computation) of every
    instruction, and each computation's ROOT opcode."""
    rows, roots, comp = [], {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s+(ROOT )?%\S+ = (.*)$", line)
        if not m:
            continue
        rest = m.group(2)
        if rest.startswith("("):                 # a tuple-shaped result
            depth = 0
            for i, c in enumerate(rest):
                depth += (c == "(") - (c == ")")
                if depth == 0:
                    break
            shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
        else:
            shape, _, rest = rest.partition(" ")
        opcode = rest.split("(", 1)[0]
        calls = re.search(r"calls=%([\w.\-]+)", rest)
        rows.append((comp, opcode, shape, calls and calls.group(1)))
        if m.group(1):
            roots[comp] = opcode
    return rows, roots


def _pool_params(text: str, pool_shape) -> set:
    """Entry parameter numbers whose type is the pool's."""
    dims = ",".join(str(d) for d in pool_shape)
    entry = text[text.index("\nENTRY "):]
    return {int(n) for n in re.findall(
        r"= bf16\[" + dims + r"\]\{[^}]*\} parameter\((\d+)\)", entry)}


@pytest.mark.parametrize("program", ["step", "insert"])
@pytest.mark.parametrize("H,K,D", [
    (20, 20, 64),          # mlecs-slm-720m: 20 heads of 64
    (16, 8, 128),          # GQA: 8 kv heads of 128
])
def test_serving_programs_keep_pool_in_place(one_chip, program, H, K, D):
    """The engine's jitted decode step and insert, at the served widths and
    pool (1280 wide, pages of 16, 1281 pages, 32 slots; two layers for a
    short compile): both pools are donated and aliased to the outputs, and
    nothing moves a pool layer's bytes or more — no ``copy`` of that size,
    and no fusion of that size but the in-place scatter into the pool."""
    cfg = dataclasses.replace(get_config("mlecs-slm-720m"), n_layers=2,
                              n_heads=H, n_kv_heads=K, head_dim=D)
    bundle = build_model(cfg)
    ec = EngineConfig(n_slots=32, page_size=16, n_pages=1281,
                      max_pages_per_seq=65, max_out=256,
                      buckets=(64, 128, 256, 512, 768, 1024))
    pstate = jax.eval_shape(
        lambda: bundle.init_paged(ec.n_slots, ec.n_pages, ec.page_size))
    pool = pstate["k_pages"]
    if program == "step":
        params = jax.eval_shape(
            lambda k: lora.merge_lora(bundle.init(k), cfg),
            jax.random.key(0))
        text = _compiled_text(jit_step(bundle, ec), one_chip, params,
                              pstate, jax.eval_shape(lambda: init_sched(ec)))
        assert "tpu_custom_call" in text         # the Pallas kernel reads it
    else:
        S = 512 + cfg.n_soft_tokens              # a bucket and the prefix
        kv = _sds((cfg.n_layers, 1, S, K, D), jnp.bfloat16)
        text = _compiled_text(jit_insert(bundle), one_chip, pstate,
                              {"k": kv, "v": kv}, _sds((), jnp.int32),
                              _sds((-(-S // ec.page_size),), jnp.int32))
    aliased = {int(p) for p in re.findall(
        r"\{[0-9]*\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}
    pools = _pool_params(text, pool.shape)
    assert len(pools) == 2 and pools <= aliased, (pools, aliased)

    layer_bytes = math.prod(pool.shape[1:]) * pool.dtype.itemsize
    rows, roots = _hlo_instructions(text)
    # instructions inside a fusion's body allocate nothing of their own
    fused = {calls for _, op, _, calls in rows if op == "fusion"}
    rows = [r for r in rows if r[0] not in fused]
    movers = [(comp, op, shape) for comp, op, shape, calls in rows
              if _largest_array(shape) >= layer_bytes
              and (op in ("copy", "copy-start")
                   or (op == "fusion" and roots.get(calls) != "scatter"))]
    assert not movers, movers
    assert any(op == "fusion" and roots.get(calls) == "scatter"
               and _largest_array(shape) >= layer_bytes
               for _, op, shape, calls in rows)   # the in-place write


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
