"""Mixture-of-Experts FFN with capacity-based expert-parallel dispatch.

TPU-native adaptation: experts are sharded on the "model" mesh axis and
tokens on "data"; the scatter/gather dispatch below lets GSPMD insert the
all-to-alls between the token-sharded and expert-sharded layouts (the same
communication pattern as GShard/MaxText dropping-MoE).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init
from repro.sharding.partition import constrain


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, E), jnp.float32),
        "we_gate": _dense_init(ks[1], (E, d, f), cfg.param_dtype),
        "we_up": _dense_init(ks[2], (E, d, f), cfg.param_dtype),
        "we_down": _dense_init(ks[3], (E, f, d), cfg.param_dtype),
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    return max(4, int(n_tokens * cfg.top_k * cfg.capacity_factor)
               // cfg.n_experts)


def moe_mlp(p, cfg: ModelConfig, x):
    """x: (B, S, d) -> (y, aux_loss).  Top-k routing, capacity C per expert."""
    Bsz, S, d = x.shape
    T = Bsz * S
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(cfg, T)

    xf = x.reshape(T, d)
    router_logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                             # (T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                    # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    # position of each (token, k) assignment within its expert's capacity
    idx_flat = idx.reshape(T * K)                                   # (TK,)
    onehot = jax.nn.one_hot(idx_flat, E, dtype=jnp.int32)           # (TK, E)
    pos_in_e = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = (pos_in_e < C)
    slot = jnp.clip(pos_in_e, 0, C - 1)

    # scatter tokens into per-expert buffers (E, C, d)
    x_rep = jnp.repeat(xf, K, axis=0) * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((E, C, d), xf.dtype).at[idx_flat, slot].add(x_rep)
    buf = constrain(buf, "act_experts", "batch", None)

    # expert FFN (grouped matmul on the MXU; experts sharded on "model")
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["we_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["we_up"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    out_e = constrain(out_e, "act_experts", "batch", None)

    # gather back and combine with gate weights
    y = out_e[idx_flat, slot]                                       # (TK, d)
    y = y * (keep[:, None] * gate.reshape(T * K)[:, None]).astype(y.dtype)
    y = y.reshape(T, K, d).sum(axis=1)
    return y.reshape(Bsz, S, d), aux


# ===========================================================================
# Expert-parallel MoE under shard_map (perf-optimized path; see
# EXPERIMENTS.md §Perf iteration 1).
#
# The auto-sharded scatter dispatch above makes GSPMD materialize the
# (T·K, E) position cumsum and the (E, C, d) buffer with conflicting
# shardings — the compiled HLO shows full-buffer all-reduces (~1.5 TB/step
# for qwen3-moe train_4k).  Here the dispatch is reformulated per device:
# tokens are sharded over "data" and replicated over "model"; every model
# rank *locally* selects the tokens routed to its E/msz experts (no
# communication at all for dispatch — the replica already holds the data),
# runs the expert FFN, scatters results back to token positions, and a
# single psum over "model" combines partial outputs — exactly one
# activation-sized all-reduce per MoE layer, the same collective a Megatron
# dense MLP pays.

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.sharding.partition import current_context  # noqa: E402


def moe_mlp_sharded(p, cfg: ModelConfig, x):
    """shard_map expert-parallel MoE.  Falls back to the auto-sharded path
    outside a sharding context (single-device tests)."""
    ctx = current_context()
    if ctx is None:
        return moe_mlp(p, cfg, x)
    mesh, rules = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msz = sizes.get("model", 1)
    if cfg.n_experts % msz != 0:
        return moe_mlp(p, cfg, x)

    batch_ax = rules.axis("batch")
    Bsz = x.shape[0]
    bsz_total = 1
    for a in (batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)):
        if a is not None:
            bsz_total *= sizes.get(a, 1)
    if Bsz % max(bsz_total, 1) != 0:
        batch_ax = None                      # e.g. long_500k batch=1

    x_spec = P(batch_ax, None, None)
    w_specs = {
        "router": P(None, None),
        "we_gate": P("model", None, None),
        "we_up": P("model", None, None),
        "we_down": P("model", None, None),
    }

    def block(router, we_gate, we_up, we_down, xb):
        B_loc, S, d = xb.shape
        T = B_loc * S
        E, K = cfg.n_experts, cfg.top_k
        E_loc = E // msz
        C = max(4, int(T * K * cfg.capacity_factor) // E)

        xf = xb.reshape(T, d)
        logits = xf.astype(jnp.float32) @ router           # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, K)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        # load-balance aux: pmean the per-expert statistics over the data
        # shards BEFORE the product (Switch aux is E.sum(me*ce) on GLOBAL
        # means; mean-of-products would differ)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                              axis=1), axis=0)
        naxes = tuple(a for a in (batch_ax if isinstance(batch_ax, tuple)
                                  else (batch_ax,)) if a)
        if naxes:
            me = jax.lax.pmean(me, naxes)
            ce = jax.lax.pmean(ce, naxes)
        aux = E * jnp.sum(me * ce)

        # local selection: which assignments belong to MY experts
        my_lo = jax.lax.axis_index("model") * E_loc
        idx_flat = idx.reshape(T * K)
        local_e = idx_flat - my_lo
        mine = (local_e >= 0) & (local_e < E_loc)
        local_e = jnp.clip(local_e, 0, E_loc - 1)
        onehot = jax.nn.one_hot(local_e, E_loc, dtype=jnp.int32) \
            * mine[:, None].astype(jnp.int32)
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = mine & (pos >= 0) & (pos < C)
        slot = jnp.clip(pos, 0, C - 1)

        x_rep = jnp.repeat(xf, K, axis=0) * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((E_loc, C, d), xf.dtype).at[local_e, slot].add(x_rep)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we_gate))
        h = h * jnp.einsum("ecd,edf->ecf", buf, we_up)
        out_e = jnp.einsum("ecf,efd->ecd", h, we_down)

        y = out_e[local_e, slot]
        y = y * (keep[:, None]
                 * gate.reshape(T * K)[:, None]).astype(y.dtype)
        y = y.reshape(T, K, d).sum(axis=1)
        # ONE activation all-reduce per layer combines expert partials
        y = jax.lax.psum(y, "model")
        return y.reshape(B_loc, S, d), aux

    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(w_specs["router"], w_specs["we_gate"], w_specs["we_up"],
                  w_specs["we_down"], x_spec),
        out_specs=(x_spec, P()),
    )(p["router"], p["we_gate"], p["we_up"], p["we_down"], x)
    return y, aux
