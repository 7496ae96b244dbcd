"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# lora_matmul

def lora_matmul_ref(x, w, a, b, scale: float):
    """y = x @ w + scale * (x @ a) @ b   (paper Eq. 1 applied at matmul)."""
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    y = y + scale * ((x.astype(jnp.float32) @ a.astype(jnp.float32))
                     @ b.astype(jnp.float32))
    return y


# ---------------------------------------------------------------------------
# gram_volume

def gram_log_volume_ref(vs, mask=None, eps: float = 1e-5):
    """Batched log-volume (paper Eq. 5-6) — mirrors repro.core.gram."""
    v = vs.astype(jnp.float32)
    v = v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-6)
    g = jnp.einsum("...kd,...ld->...kl", v, v)
    k = g.shape[-1]
    if mask is not None:
        m = mask[..., :, None] & mask[..., None, :]
        g = jnp.where(m, g, jnp.eye(k, dtype=jnp.float32))
    g = g + eps * jnp.eye(k, dtype=jnp.float32)
    chol = jnp.linalg.cholesky(g)
    return jnp.sum(jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)), axis=-1)


# ---------------------------------------------------------------------------
# flash attention

def attention_ref(q, k, v, causal: bool = True,
                  window: Optional[int] = None):
    """q: (B,H,Sq,D)  k,v: (B,H,Sk,D) (kv already repeated to H heads)."""
    D = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    Sq, Sk = q.shape[2], k.shape[2]
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)   # align ends
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None and window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# paged decode attention

def paged_attention_ref(q, k_pages, v_pages, layer, block_tables, lens,
                        window: Optional[int] = None):
    """Decode-mode oracle.  q: (B,1,H,D);  k_pages/v_pages: (L,P,ps,K*D),
    the kv heads of an entry side by side in its row;  layer: the pool's
    layer to read;  block_tables: (B,M) page ids;  lens: (B,) valid entries
    incl. the newest token.  KV heads are grouped (GQA); idle slots (len 0)
    return zeros.  Returns (B, 1, H, D)."""
    B, _, H, D = q.shape
    ps, K = k_pages.shape[2], k_pages.shape[3] // D
    M = block_tables.shape[1]
    G = H // K
    # gather each request's logical KV sequence: (B, M*ps, K, D)
    kl, vl = k_pages[layer], v_pages[layer]
    k = kl[block_tables].reshape(B, M * ps, K, D).astype(jnp.float32)
    v = vl[block_tables].reshape(B, M * ps, K, D).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(B, K, G, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf, k) / math.sqrt(D)
    qpos = lens[:, None] - 1                               # (B,1)
    kpos = jnp.arange(M * ps)[None, :]                     # (1,S)
    mask = kpos <= qpos
    if window is not None and window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(lens[:, None, None, None] > 0, w, 0.0)   # idle slots
    out = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# quantize / dequantize (wire codec tiles)

def quantize_ref(x, qmax: int):
    """Per-row symmetric abs-max quantization oracle.

    x: (R, L) — each row is one wire tile.  Returns ``(q, scale)`` with
    ``q`` int8 in [-qmax, qmax] and ``scale`` f32 (R,) such that
    ``q * scale`` reconstructs the row to within scale/2 per element.
    All-zero rows get scale 0 and quantize to exact zeros (the padded-row
    case), so dequantize(quantize(0)) == 0 without a special case.

    The scale is DEFINED as ``absmax * (1/qmax)`` — a single f32 multiply
    — rather than ``absmax / qmax``: XLA strength-reduces division by a
    constant to a reciprocal multiply in some lowerings but not others,
    so the divide form is one ULP away from itself across eager / jit /
    Pallas-interpret contexts, breaking the bitwise kernel-vs-twin pin.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) * jnp.float32(1.0 / qmax)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[:, None]), -qmax, qmax)
    return q.astype(jnp.int8), scale


def dequantize_ref(q, scale):
    """Inverse of :func:`quantize_ref`: (R, L) int8 + (R,) f32 -> (R, L) f32."""
    return q.astype(jnp.float32) * scale[:, None]


# ---------------------------------------------------------------------------
# ssd intra-chunk

def ssd_chunk_ref(x, dt, cum, B_, C_):
    """Intra-chunk SSD term + end-of-chunk state for ONE chunk.

    x: (L,P)  dt: (L,)  cum: (L,) cumulative a=dt*A  B_,C_: (L,N)
    y[i] = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    state = sum_j exp(cum_L - cum_j) dt_j outer(x_j, B_j)
    """
    L = x.shape[0]
    f32 = jnp.float32
    x, dt, cum, B_, C_ = (t.astype(f32) for t in (x, dt, cum, B_, C_))
    diff = cum[:, None] - cum[None, :]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(causal, jnp.exp(diff), 0.0)
    att = (C_ @ B_.T) * decay * dt[None, :]
    y = att @ x
    decay_end = jnp.exp(cum[-1] - cum)
    state = jnp.einsum("l,lp,ln->pn", decay_end * dt, x, B_)
    return y, state


def ssd_recurrent_ref(x, dt, A, B_, C_):
    """Brute-force token-by-token SSD recurrence — ground truth for the
    chunked algorithm itself.  x: (B,S,H,P)  dt: (B,S,H)  B_,C_: (B,S,G,N)."""
    Bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(B_, rep, axis=2).astype(f32)
    Ch = jnp.repeat(C_, rep, axis=2).astype(f32)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt.astype(f32) * A)               # (B,H)
        h = h * decay[:, :, None, None] \
            + (dtt.astype(f32)[:, :, None] * xt.astype(f32))[..., None] \
            * bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), f32)
    _, ys = jax.lax.scan(step, h0,
                         (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                          Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3)))
    return ys.transpose(1, 0, 2, 3)
