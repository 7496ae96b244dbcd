"""Quantize/dequantize Pallas kernel pair — the wire-codec hot path.

The communication channel (``repro.core.channel``) flattens every uplink
leaf into (R, block) tiles and quantizes each tile symmetrically against
its own abs-max.  On TPU that encode sits inside the jitted device phase,
so it is written as a Pallas kernel: the grid tiles the row axis, each
step streams a (br, block) slab through VMEM, reduces the per-row abs-max
on the VPU and emits the int8 codes plus one f32 scale per row — one HBM
read of the floats, one (eighth-sized) write of the codes.  Inside the
kernels the scales are a 2-D ``(R, 1)`` column with ``(br, 1)`` blocks: a
1-D ``(br,)`` block only lowers while ``br`` equals the whole array, which
breaks the moment R exceeds one block.  CPU runs the
pure-jnp twin in ``repro.kernels.ops`` instead (per the paged-attention
precedent); both are pinned to ``ref.quantize_ref``/``dequantize_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: int):
    x = x_ref[...].astype(jnp.float32)                 # (br, block)
    # scale := absmax * (1/qmax), one f32 multiply — see ref.quantize_ref
    # for why the divide form is not reproducible across lowerings
    scale = (jnp.max(jnp.abs(x), axis=-1, keepdims=True)
             * jnp.float32(1.0 / qmax))                # (br, 1)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                 # (br, block)
    o_ref[...] = q * s_ref[...]                        # (br, 1) scales


@functools.partial(jax.jit, static_argnames=("qmax", "br", "interpret"))
def quantize_rows(x, qmax: int = 127, br: int = 128, interpret: bool = True):
    """x: (R, L) floats, one tile per row -> (int8 (R, L), f32 scales (R,)).

    R must be a multiple of ``br`` — the public wrapper in ``ops`` pads
    with all-zero rows (scale 0, sliced off) for the general case.
    """
    R, L = x.shape
    br = min(br, R)
    assert R % br == 0
    kernel = functools.partial(_quant_kernel, qmax=qmax)
    q, s = pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, L), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, L), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, L), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s.reshape(R)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def dequantize_rows(q, scale, br: int = 128, interpret: bool = True):
    """Inverse of :func:`quantize_rows`: (R, L) int8 + (R,) f32 -> f32."""
    R, L = q.shape
    br = min(br, R)
    assert R % br == 0
    return pl.pallas_call(
        _dequant_kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, L), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, L), jnp.float32),
        interpret=interpret,
    )(q, scale.reshape(R, 1))
