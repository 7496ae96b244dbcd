"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on real TPU
backends — the kernels are written for TPU (pl.pallas_call + BlockSpec VMEM
tiling) and *validated* in interpret mode against the pure-jnp oracles in
``ref.py``.  The serving and wire-codec wrappers (``paged_attention``,
``quantize``, ``dequantize``) choose kernel or jnp twin by the platform a
computation is compiled for (``_kernel_or_twin``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gram_volume import gram_log_volume as _gram
from repro.kernels.lora_matmul import lora_matmul as _lora
from repro.kernels.paged_attention import paged_flash_attention as _paged
from repro.kernels.quantize import dequantize_rows as _dequant
from repro.kernels.quantize import quantize_rows as _quant
from repro.kernels.ssd_scan import ssd_chunk as _ssd_chunk


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel_or_twin(kernel, twin, use_kernel, interpret):
    """``kernel(interpret)`` or ``twin()``.  ``use_kernel`` None decides per
    compile target: the Pallas kernel where the computation is lowered for
    a TPU, the jnp twin anywhere else.  The choice is made at lowering
    (``lax.platform_dependent``), not from the process's default backend,
    so a program compiled for a TPU holds the kernel even when it is
    compiled from a host without one."""
    if use_kernel is None:
        return jax.lax.platform_dependent(
            tpu=lambda: kernel(bool(interpret)), default=twin)
    if use_kernel:
        return kernel(default_interpret() if interpret is None
                      else interpret)
    return twin()


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              bq: int = 128, bk: int = 128, interpret=None):
    """GQA-aware flash attention.  q: (B,Sq,H,D)  k,v: (B,Sk,K,D) —
    model-layout (seq before heads); handles the head expansion."""
    interpret = default_interpret() if interpret is None else interpret
    B, Sq, H, D = q.shape
    K = k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    out = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                 v.transpose(0, 2, 1, 3), causal=causal, window=window,
                 bq=bq, bk=bk, interpret=interpret)
    return out.transpose(0, 2, 1, 3).reshape(B, Sq, H * D)


def paged_attention(q, k_pages, v_pages, block_tables, lens, window, *,
                    use_kernel=None, interpret=None):
    """Decode-mode (Sq=1) attention over a paged KV cache, GQA-aware.

    q: (B, 1, H, D) model layout;  k_pages/v_pages: (P, ps, K, D);
    block_tables: (B, M) int32 page ids per logical block;  lens: (B,) int32
    valid entries per slot INCLUDING the newest token (0 = idle slot);
    window: scalar int32 (layers.BIG_WINDOW = none; may be traced — the
    per-layer window rides through the model's layer scan).

    Returns (B, 1, H * D).  ``use_kernel`` None = kernel when compiled for
    TPU, pure-jnp gather path elsewhere (the Pallas grid walks one page per
    step, which interpret mode would execute as a Python loop — correct
    but slow; the jnp path is the serving fast path on CPU and the
    oracle's twin).
    """
    B, _, H, D = q.shape
    ps, K = k_pages.shape[1], k_pages.shape[2]
    M = block_tables.shape[1]

    def kernel(interp):
        out = _paged(q, k_pages, v_pages, block_tables, lens, window,
                     interpret=interp)
        return out.reshape(B, 1, H * D)

    def twin():
        # mha math inlined (models.layers imports would cycle)
        G = H // K
        import math as _math
        k = k_pages[block_tables].reshape(B, M * ps, K, D).astype(jnp.float32)
        v = v_pages[block_tables].reshape(B, M * ps, K, D).astype(jnp.float32)
        qf = q.astype(jnp.float32).reshape(B, K, G, D)
        logits = jnp.einsum("bkgd,bskd->bkgs", qf, k) / _math.sqrt(D)
        qpos = lens[:, None] - 1
        kpos = jnp.arange(M * ps, dtype=jnp.int32)[None, :]
        mask = (kpos <= qpos) & (qpos - kpos < window)
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        w = jnp.where(lens[:, None, None, None] > 0, w, 0.0)
        out = jnp.einsum("bkgs,bskd->bkgd", w, v)
        return out.reshape(B, 1, H * D).astype(q.dtype)

    return _kernel_or_twin(kernel, twin, use_kernel, interpret)


def gram_log_volume(vs, mask=None, eps: float = 1e-5, interpret=None):
    """Batched masked log-volume.  The kernel grid needs the batch to be a
    multiple of the block size, so batches over 128 rows are padded up to
    the next multiple of 128 with all-masked rows (the kernel's pair mask
    turns them into identity Grams, sliced off afterwards) — a prime B of
    e.g. 131 costs one extra 128-row block, not a degenerate bb=1 grid of
    one step per row."""
    interpret = default_interpret() if interpret is None else interpret
    B, k = vs.shape[0], vs.shape[1]
    if mask is None:
        mask = jnp.ones((B, k), jnp.bool_)
    bb = B if B <= 128 else 128
    pad = -B % bb
    if pad:
        vs = jnp.concatenate(
            [vs, jnp.zeros((pad,) + vs.shape[1:], vs.dtype)])
        mask = jnp.concatenate(
            [mask, jnp.zeros((pad, k), mask.dtype)])
    out = _gram(vs, mask, eps=eps, bb=bb, interpret=interpret)
    return out[:B] if pad else out


def quantize(x, qmax: int = 127, *, use_kernel=None, interpret=None):
    """Per-row symmetric abs-max quantization.  x: (R, L) — one wire tile
    per row — returns ``(q int8 (R, L), scale f32 (R,))``.

    ``use_kernel`` None = Pallas kernel when compiled for TPU, pure-jnp twin
    elsewhere (the twin IS the oracle math, so CPU engine parity is exact).
    The kernel grid needs R to be a multiple of the 128-row block, so
    prime row counts are padded with all-zero rows (scale 0, codes 0) and
    sliced off — same precedent as ``gram_log_volume``.
    """
    def kernel(interp):
        R = x.shape[0]
        br = R if R <= 128 else 128
        pad = -R % br
        xp = (jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
              if pad else x)
        q, s = _quant(xp, qmax=qmax, br=br, interpret=interp)
        return (q[:R], s[:R]) if pad else (q, s)

    def twin():
        xf = x.astype(jnp.float32)
        # scale := absmax * (1/qmax) — bitwise-pinned to ref.quantize_ref
        scale = jnp.max(jnp.abs(xf), axis=-1) * jnp.float32(1.0 / qmax)
        safe = jnp.where(scale > 0.0, scale, 1.0)
        q = jnp.clip(jnp.round(xf / safe[:, None]), -qmax, qmax)
        return q.astype(jnp.int8), scale

    return _kernel_or_twin(kernel, twin, use_kernel, interpret)


def dequantize(q, scale, *, use_kernel=None, interpret=None):
    """Inverse of :func:`quantize`: (R, L) int8 + (R,) f32 scales -> f32."""
    def kernel(interp):
        R = q.shape[0]
        br = R if R <= 128 else 128
        pad = -R % br
        qp, sp = q, scale
        if pad:
            qp = jnp.concatenate([q, jnp.zeros((pad, q.shape[1]), q.dtype)])
            sp = jnp.concatenate([scale, jnp.zeros((pad,), scale.dtype)])
        out = _dequant(qp, sp, br=br, interpret=interp)
        return out[:R] if pad else out

    def twin():
        return q.astype(jnp.float32) * scale[:, None]

    return _kernel_or_twin(kernel, twin, use_kernel, interpret)


def lora_matmul(x, w, a, b, scale: float = 1.0, interpret=None, **blocks):
    interpret = default_interpret() if interpret is None else interpret
    return _lora(x, w, a, b, scale=scale, interpret=interpret, **blocks)


def ssd_chunked(x, dt, A, B_, C_, chunk: int, interpret=None):
    """Full SSD over (B,S,...) using the intra-chunk kernel + jnp recurrence.
    Same contract as models.ssm.ssd_reference."""
    interpret = default_interpret() if interpret is None else interpret
    Bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    nc, L = S // chunk, chunk
    rep = H // G

    f32 = jnp.float32
    xc = x.reshape(Bsz * nc, L, H, P).transpose(0, 2, 1, 3)
    dtc = dt.reshape(Bsz * nc, L, H).transpose(0, 2, 1).astype(f32)
    Bc = jnp.repeat(B_.reshape(Bsz * nc, L, G, N), rep, axis=2) \
        .transpose(0, 2, 1, 3)
    Cc = jnp.repeat(C_.reshape(Bsz * nc, L, G, N), rep, axis=2) \
        .transpose(0, 2, 1, 3)
    da = dtc * A[None, :, None]
    cum = jnp.cumsum(da, axis=-1)

    y_intra, states = _ssd_chunk(xc, dtc, cum, Bc, Cc, interpret=interpret)

    # inter-chunk recurrence in jnp (cheap): states (B*nc, H, P, N)
    states = states.reshape(Bsz, nc, H, P, N)
    total = cum[:, :, -1].reshape(Bsz, nc, H)

    def step(h, inp):
        st, tot = inp
        return jnp.exp(tot)[:, :, None, None] * h + st, h
    h0 = jnp.zeros((Bsz, H, P, N), f32)
    _, h_prev = jax.lax.scan(step, h0, (states.transpose(1, 0, 2, 3, 4),
                                        total.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)            # (B,nc,H,P,N)

    y_inter = jnp.einsum("bchln,bchpn->bchlp",
                         Cc.reshape(Bsz, nc, H, L, N)
                         * jnp.exp(cum).reshape(Bsz, nc, H, L)[..., None],
                         h_prev)
    y = y_intra.reshape(Bsz, nc, H, L, P) + y_inter
    return y.transpose(0, 1, 3, 2, 4).reshape(Bsz, S, H, P).astype(x.dtype)
