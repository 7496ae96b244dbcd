"""Paged (blocked) KV-cache serving contract for every model family.

The training-era decode path gives each request a private contiguous cache of
``init_cache(B, S + max_new)`` and copies the prefill cache into it
(``launch.serve._reseat_cache``).  That couples cache capacity to the longest
request in the batch and forces a full reallocation whenever the batch
composition changes — exactly what continuous batching cannot afford.  Here
the KV cache is a pool of fixed-size **pages** shared by all decode slots:

  k_pages / v_pages : (L, n_pages, page_size, K * hd)  physical pool
  block_tables      : (n_slots, max_pages) int32        logical -> physical

One cache entry is one row of ``K * hd`` values, its kv heads side by side.
That minor dimension is dense on the TPU's (16, 128) bf16 tiles wherever
``K * hd`` is a multiple of 128 (a ``(.., K, hd)`` pool with hd = 64 pads
every tile to twice its size and more), so the compiler keeps the pool in
its row-major layout and three users share it: the decode step's in-place
token write, the Pallas kernel's page DMA, and the insert's page scatter.
The pool never moves.  The decode step carries it through the layer scan
(the scan's carry, not its ``xs``/``ys``, which would slice and restack
every layer), writes each layer's new token with one scatter at
``[layer, page, pos % page_size]`` and hands the kernel the whole pool and
the layer index; the serving engine donates it to the step and the insert,
so each updates the one buffer in place.

Page 0 is reserved as a **scratch page** (the allocator never hands it out):
idle slots keep an all-zero block-table row, so the unconditional per-step
cache write inside the jitted engine step lands harmlessly on page 0 instead
of needing a ``lax.cond`` per slot.

Per-family state beyond the pages (all keyed per *slot*, not per page):

  hybrid   ssm_h (L, n_slots, H, P, N) f32 + ssm_conv (L, n_slots, W-1, C)
  ssm      recurrent state only — zero pages, the block table is unused
  encdec   cross_k / cross_v (L, n_slots, T, K, hd) — dense per-slot
           (T = cfg.frontend_tokens frames, same for every request)

Contract (wired into :class:`repro.models.model.ModelBundle`):

  init_paged(cfg, n_slots, n_pages, page_size)      -> pstate
  prefill_paged(params, cfg, batch, true_len)       -> (last_logits, pack, kv_len)
  insert_paged(cfg, pstate, pack, slot, page_ids)   -> pstate
  decode_paged(params, cfg, pstate, block_tables,
               seq_lens, tokens, active)            -> (logits, pstate)

``prefill_paged`` accepts right-padded prompts (``tokens`` padded to a
compile bucket, ``true_len`` the real length, traced) for the attention
families — causal masking keeps positions < true_len blind to the garbage
tail, and decode overwrites the tail's pages one token at a time.  The
recurrent families (ssm, hybrid) must be fed exact lengths: padded tokens
would be folded into the SSM state.  The serving engine enforces this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import encdec as encdec_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import transformer


def pages_for(length: int, page_size: int) -> int:
    """Pages needed to hold ``length`` cache entries."""
    return -(-length // page_size)


def _prefix(params, cfg, batch):
    from repro.models.model import _prefix as mp
    return mp(params, cfg, batch)


# ---------------------------------------------------------------------------
# state allocation

def init_paged(cfg: ModelConfig, n_slots: int, n_pages: int,
               page_size: int) -> dict:
    K, hd, Lr = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    pstate = {}
    if cfg.family != "ssm":
        pstate["k_pages"] = jnp.zeros((Lr, n_pages, page_size, K * hd),
                                      cfg.param_dtype)
        pstate["v_pages"] = jnp.zeros((Lr, n_pages, page_size, K * hd),
                                      cfg.param_dtype)
    if cfg.family in ("ssm", "hybrid"):
        st = ssm_lib.init_ssm_state(cfg, n_slots)
        pstate["ssm_h"] = jnp.zeros((Lr, *st["h"].shape), jnp.float32)
        pstate["ssm_conv"] = jnp.zeros((Lr, *st["conv"].shape),
                                       cfg.param_dtype)
    if cfg.family == "encdec":
        T = cfg.frontend_tokens
        pstate["cross_k"] = jnp.zeros((Lr, n_slots, T, K, hd),
                                      cfg.param_dtype)
        pstate["cross_v"] = jnp.zeros((Lr, n_slots, T, K, hd),
                                      cfg.param_dtype)
    return pstate


# ---------------------------------------------------------------------------
# prefill -> per-request pack

def prefill_paged(params, cfg: ModelConfig, batch: dict, true_len):
    """Full forward over a (possibly right-padded) prompt.

    Returns (last_logits (B, V) at the TRUE last position, a pack of
    per-request cache leaves, and kv_len = prefix + true_len — the number of
    cache entries the request actually owns after insertion).
    """
    tokens = batch["tokens"]
    if cfg.family == "encdec":
        enc_x = encdec_lib.encode(params, cfg, batch["frontend_embeds"])
        logits, kv = encdec_lib.decode_forward(params, cfg, tokens, enc_x,
                                               collect_kv=True)
        xk, xv = encdec_lib.encode_cross_kv(params, cfg, enc_x)
        last = jnp.take(logits, true_len - 1, axis=1)
        pack = {"k": kv[0], "v": kv[1], "cross_k": xk, "cross_v": xv}
        return last, pack, jnp.int32(true_len)

    if cfg.family == "ssm":
        logits, _, states = ssm_lib.forward(params, cfg, tokens,
                                            _prefix(params, cfg, batch),
                                            collect_state=True)
        P = logits.shape[1] - tokens.shape[1]
        last = jnp.take(logits, P + true_len - 1, axis=1)
        return last, {"ssm_h": states[0], "ssm_conv": states[1]}, \
            jnp.int32(P + true_len)

    logits, _, kv = transformer.forward(params, cfg, tokens,
                                        _prefix(params, cfg, batch),
                                        collect_kv=True)
    P = logits.shape[1] - tokens.shape[1]
    last = jnp.take(logits, P + true_len - 1, axis=1)
    pack = {"k": kv[0], "v": kv[1]}
    if cfg.family == "hybrid":
        pack["ssm_h"], pack["ssm_conv"] = kv[2], kv[3]
    return last, pack, jnp.int32(P + true_len)


# ---------------------------------------------------------------------------
# insertion (one request, B = 1)

def insert_paged(cfg: ModelConfig, pstate: dict, pack: dict, slot,
                 page_ids) -> dict:
    """Seat a B=1 prefill pack: KV scattered into ``page_ids`` (static count
    covering the padded prompt), per-slot leaves written at ``slot``."""
    out = dict(pstate)
    if "k" in pack:
        kp = pstate["k_pages"]
        ps = kp.shape[2]
        n_used = page_ids.shape[0]
        for src, dst in (("k", "k_pages"), ("v", "v_pages")):
            t = pack[src][:, 0]                       # (L, S, K, hd)
            Lr, S = t.shape[0], t.shape[1]
            t = t.reshape(Lr, S, -1)                  # (L, S, K * hd)
            pad = n_used * ps - S
            if pad:
                t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
            t = t.reshape(Lr, n_used, ps, t.shape[-1])
            out[dst] = pstate[dst].at[:, page_ids].set(
                t.astype(pstate[dst].dtype))
    for name in ("ssm_h", "ssm_conv", "cross_k", "cross_v"):
        if name in pack:
            out[name] = pstate[name].at[:, slot].set(
                pack[name][:, 0].astype(pstate[name].dtype))
    return out


# ---------------------------------------------------------------------------
# decode

def _paged_decode_attention(ap, cfg: ModelConfig, h, pos_vec, kp, vp, layer,
                            block_tables, lens_incl, window, use_kernel):
    """One-token self-attention of ``layer`` against the whole paged pool.
    Writes the new K/V in place at position ``pos_vec[b]`` of slot b's
    logical sequence (idle slots hit scratch page 0 via their zeroed
    block-table row), then attends."""
    q, k_new, v_new = L._qkv(ap, cfg, h, h, pos_vec[:, None], pos_vec[:, None])
    B, ps = pos_vec.shape[0], kp.shape[2]
    blk = pos_vec // ps
    page = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    kp = kp.at[layer, page, pos_vec % ps].set(
        k_new.reshape(B, -1).astype(kp.dtype))
    vp = vp.at[layer, page, pos_vec % ps].set(
        v_new.reshape(B, -1).astype(vp.dtype))
    out = ops.paged_attention(q, kp, vp, layer, block_tables, lens_incl,
                              window, use_kernel=use_kernel)
    return L.proj(ap, "wo", out, cfg), kp, vp


def decode_paged(params, cfg: ModelConfig, pstate: dict, block_tables,
                 seq_lens, tokens, active, use_kernel=None):
    """One token for every slot.  tokens: (n_slots, 1); seq_lens: (n_slots,)
    cached entries per slot (the new token lands at that position);
    active: (n_slots,) bool.  Returns (logits (n_slots, V), new pstate)."""
    if cfg.family == "ssm":
        cache = {"ssm_h": pstate["ssm_h"], "ssm_conv": pstate["ssm_conv"]}
        logits, new = ssm_lib.decode_step(params, cfg, cache, tokens,
                                          jnp.int32(0))
        return logits, dict(pstate, **new)

    x = L.embed(params["tok"], cfg, tokens)
    pos_vec = seq_lens.astype(jnp.int32)
    lens_incl = jnp.where(active, seq_lens + 1, 0).astype(jnp.int32)
    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    # the pools ride in the carry: each layer writes and reads them in place
    carry0 = (x, pstate["k_pages"], pstate["v_pages"])

    if cfg.family == "encdec":
        window = jnp.int32(cfg.sliding_window or L.BIG_WINDOW)

        def body(carry, xs):
            x, kp, vp = carry
            lp, layer, xk, xv = xs
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            attn_out, kp, vp = _paged_decode_attention(
                lp["attn"], cfg, h, pos_vec, kp, vp, layer, block_tables,
                lens_incl, window, use_kernel)
            y = x + attn_out
            hx = L.rms_norm(y, lp["ln_x"], cfg.norm_eps)
            y = y + L.cross_attention(lp["xattn"], cfg, hx, xk, xv)
            h2 = L.rms_norm(y, lp["ln2"], cfg.norm_eps)
            y = y + L.mlp(lp["mlp"], cfg, h2)
            return (y, kp, vp), None

        (x, kp, vp), _ = jax.lax.scan(
            body, carry0, (params["dec_layers"], layer_ids,
                           pstate["cross_k"], pstate["cross_v"]))
        new_pstate = dict(pstate, k_pages=kp, v_pages=vp)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return L.unembed(params["tok"], cfg, x)[:, 0], new_pstate

    windows = transformer.window_array(cfg)
    hybrid = cfg.family == "hybrid"

    def body(carry, xs):
        x, kp, vp = carry
        if hybrid:
            lp, layer, w, sh, sconv = xs
        else:
            lp, layer, w = xs
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        attn_out, kp, vp = _paged_decode_attention(
            lp["attn"], cfg, h, pos_vec, kp, vp, layer, block_tables,
            lens_incl, w, use_kernel)
        new_state = ()
        if hybrid:
            ssm_out, new_state = ssm_lib.ssm_decode_step(
                lp["ssm"], cfg, {"h": sh, "conv": sconv}, h)
            attn_out = 0.5 * (attn_out + ssm_out)
        y = x + attn_out
        h2 = L.rms_norm(y, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            moe_fn = (moe_lib.moe_mlp_sharded if cfg.moe_impl == "sharded"
                      else moe_lib.moe_mlp)
            m, _ = moe_fn(lp["moe"], cfg, h2)
        else:
            m = L.mlp(lp["mlp"], cfg, h2)
        y = y + m
        if hybrid:
            return (y, kp, vp), (new_state["h"], new_state["conv"])
        return (y, kp, vp), None

    xs = (params["layers"], layer_ids, windows)
    if hybrid:
        xs = xs + (pstate["ssm_h"], pstate["ssm_conv"])
    (x, kp, vp), ys = jax.lax.scan(body, carry0, xs)
    new_pstate = dict(pstate, k_pages=kp, v_pages=vp)
    if hybrid:
        new_pstate["ssm_h"], new_pstate["ssm_conv"] = ys
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(params["tok"], cfg, x)[:, 0], new_pstate
