"""The plain reference of the dense decoder backbone: pre-norm RMSNorm,
rotary positions, grouped-query causal attention, a GeLU or SiLU-gated MLP,
LoRA on the attention projections, tied embeddings.

A reference module supplies the backbone of one model family, and nothing
of ML-ECS (the connector, the personal leaves, the losses live in
``bench/model.py`` and work for any backbone).  A configuration's model
names its module with ``"reference": "<path under bench/>"``; a model
without the key takes this one.  Each module defines:

- ``backbone_shapes(m)``: ``{path: (shape, std)}`` of the frozen backbone in
  the program's layout ('/'-joined paths; std None = zeros);
- ``lora_dims(m)``: ``{base weight path: (stacked layers, in, out)}`` of the
  projections ``m["lora_targets"]`` adapts; the adapters are
  ``<path>_lora_a`` ``(layers, in, rank)`` and ``<path>_lora_b``
  ``(layers, rank, out)``;
- ``forward(p, m, tokens, prefix=None, prec="f32")``: logits (B, P+S, V) of
  nested params ``p`` over ``tokens`` behind an optional embedding prefix,
  in float32 with matmuls at ``Precision.HIGHEST``; ``prec="fp8"`` rounds
  every matmul operand to float8 e4m3 first (the lower-precision control);
- ``forward_at(p, m, tokens, prefix, where, prec="f32")``: logits (B, n, V)
  at positions ``where`` (B, n) only;
- ``matmul_weights(m)``: weights one token meets in the backbone's matmuls
  over all layers, LoRA and the logits not counted;
- ``pair_flops(m)``: FLOPs of one causal (query, key) pair over all layers
  in a forward pass (scores and weighted values);
- ``kv_layers(m)``: layers that keep a paged K/V cache, each one
  paged-attention call a decode step;
- ``live_kv_bytes(m, lens, page_size, itemsize=2)``: bytes one paged decode
  step moves for slots holding ``lens`` cached entries.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.model import F32, ein, gelu, mm, padded_vocab, rms, rope


def backbone_shapes(m: dict) -> dict:
    d, H, K, hd, f, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"], m["n_layers"])
    s = {"tok/embed": ((padded_vocab(m), d), 1.0 / math.sqrt(d)),
         "final_norm": ((d,), None),
         "layers/ln1": ((L, d), None), "layers/ln2": ((L, d), None),
         "layers/attn/wq": ((L, d, H * hd), 1.0 / math.sqrt(d)),
         "layers/attn/wk": ((L, d, K * hd), 1.0 / math.sqrt(d)),
         "layers/attn/wv": ((L, d, K * hd), 1.0 / math.sqrt(d)),
         "layers/attn/wo": ((L, H * hd, d), 1.0 / math.sqrt(H * hd)),
         "layers/mlp/w_up": ((L, d, f), 1.0 / math.sqrt(d)),
         "layers/mlp/w_down": ((L, f, d), 1.0 / math.sqrt(f))}
    if m.get("qk_norm"):
        s["layers/attn/q_norm"] = ((L, hd), None)
        s["layers/attn/k_norm"] = ((L, hd), None)
    if m["activation"] in ("silu", "geglu"):
        s["layers/mlp/w_gate"] = ((L, d, f), 1.0 / math.sqrt(d))
    return s


def lora_dims(m: dict) -> dict:
    d, H, K, hd, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["n_layers"])
    dims = {"wq": (d, H * hd), "wk": (d, K * hd), "wv": (d, K * hd),
            "wo": (H * hd, d)}
    return {f"layers/attn/{t}": (L,) + dims[t] for t in m["lora_targets"]}


# ---------------------------------------------------------------------------
# the forward pass

def _lin(ap, name, x, m, prec):
    y = mm(x, ap[name], prec)
    a = ap.get(f"{name}_lora_a")
    if a is not None:
        y = y + (m["lora_alpha"] / m["lora_rank"]) * mm(
            mm(x, a, prec), ap[f"{name}_lora_b"], prec)
    return y


def _layer(m, prec, x, lp, pos):
    B, S, _ = x.shape
    H, K, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m.get("norm_eps", 1e-6)
    ap = lp["attn"]
    h = rms(x, lp["ln1"], eps)
    q = _lin(ap, "wq", h, m, prec).reshape(B, S, H, D)
    k = _lin(ap, "wk", h, m, prec).reshape(B, S, K, D)
    v = _lin(ap, "wv", h, m, prec).reshape(B, S, K, D)
    if m.get("qk_norm"):
        q, k = rms(q, ap["q_norm"], eps), rms(k, ap["k_norm"], eps)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    q = q.reshape(B, S, K, H // K, D)
    s = ein("bqkgd,bskd->bkgqs", q, k, prec) / math.sqrt(D)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = ein("bkgqs,bskd->bqkgd", w, v, prec).reshape(B, S, H * D)
    x = x + _lin(ap, "wo", o, m, prec)
    h2 = rms(x, lp["ln2"], eps)
    mp = lp["mlp"]
    up = mm(h2, mp["w_up"], prec)
    if m["activation"] == "silu":
        g = mm(h2, mp["w_gate"], prec)
        hid = g * jax.nn.sigmoid(g) * up
    else:
        hid = gelu(up)
    return x + mm(hid, mp["w_down"], prec)


def hidden(p, m: dict, tokens, prefix=None, prec: str = "f32"):
    """Final-norm hidden states (B, P+S, d)."""
    x = jnp.take(p["tok"]["embed"], tokens, axis=0).astype(F32)
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(F32), x], axis=1)
    pos = jnp.arange(x.shape[1])

    @jax.checkpoint
    def body(x, lp):
        return _layer(m, prec, x, lp, pos), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    return rms(x, p["final_norm"], m.get("norm_eps", 1e-6))


def forward(p, m: dict, tokens, prefix=None, prec: str = "f32"):
    return mm(hidden(p, m, tokens, prefix, prec), p["tok"]["embed"].T, prec)


def forward_at(p, m: dict, tokens, prefix, where, prec: str = "f32"):
    h = hidden(p, m, tokens, prefix, prec)
    h = jnp.take_along_axis(h, where[..., None], axis=1)
    return mm(h, p["tok"]["embed"].T, prec)


# ---------------------------------------------------------------------------
# operations and bytes

def matmul_weights(m: dict) -> int:
    d, H, K, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    mult = 3 if m["activation"] in ("silu", "geglu") else 2
    return m["n_layers"] * (d * (H + 2 * K) * hd + H * hd * d + mult * d * f)


def pair_flops(m: dict) -> int:
    return 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]


def kv_layers(m: dict) -> int:
    return m["n_layers"]


def live_kv_bytes(m: dict, lens, page_size: int, itemsize: int = 2) -> int:
    """The live K/V pages of each active slot, plus its query and output
    rows, over every layer."""
    K, H, hd, L = m["n_kv_heads"], m["n_heads"], m["head_dim"], m["n_layers"]
    pages = sum(math.ceil(x / page_size) for x in lens)
    kv = pages * page_size * K * hd * 2 * itemsize
    qo = len(lens) * H * hd * 2 * itemsize
    return (kv + qo) * L
