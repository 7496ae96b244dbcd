"""Decode-mode (Sq=1) flash attention over a paged KV cache — the serving
engine's hot kernel.

The KV cache lives in fixed-size pages ``(n_pages, page_size, K, D)`` shared
by all requests; each request owns an ordered list of page ids (its *block
table*).  The kernel never materializes a request's contiguous KV: the grid's
inner axis walks the block table and the BlockSpec index_map — fed by
scalar-prefetched block tables (``pltpu.PrefetchScalarGridSpec``) — DMAs the
right physical page for each logical block.  Online softmax accumulates in
VMEM scratch exactly like the prefill kernel in ``flash_attention.py``.

Grid: ``(batch_slots, max_pages_per_seq)``.  One grid step moves one whole
page ``(page_size, K, D)`` for ALL kv heads, so every block's last two dims
are the full ``(K, D)`` of the pool — the TPU tiling rule (last two block
dims divisible by 8 and 128, or equal to the array's) holds for any head
count and head width, and the pool keeps the layout ``models/paged.py``
writes.  GQA needs no KV repeat: the wrapper regroups the query heads as
``(G, K, D)`` (group-major), and the kernel scores group ``g``'s ``(K, D)``
queries against the page with a lane reduction (VPU work; one query token
per slot makes decode memory bound, not MXU bound).  Pages entirely past a
request's length are skipped with ``pl.when`` and their index_map repeats
the last live page, so no DMA is issued for them (an idle slot with
``len == 0`` skips every page and returns zeros).

The sliding window arrives as a scalar-prefetch operand rather than a static
kernel parameter because the per-layer window is a traced value inside the
model's layer scan (gemma3's 5-local:1-global pattern).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, ps: int, groups: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = len_ref[b] - 1                       # position of the new token

    # skip pages entirely past the sequence (and everything for idle slots)
    @pl.when(j * ps <= qpos)
    def _compute():
        k = k_ref[0].astype(jnp.float32)        # (ps, K, D)
        v = v_ref[0].astype(jnp.float32)        # (ps, K, D)
        shp = (k.shape[0], k.shape[1], 1)
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, shp, 0)
        mask = (kpos <= qpos) & (qpos - kpos < win_ref[0])
        inv = 1.0 / math.sqrt(k.shape[-1])
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32)             # (K, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * inv
            s = jnp.where(mask, s, NEG_INF)                 # (ps, K, 1)
            m_prev = m_scr[g]                               # (K, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[None])                    # (ps, K, 1)
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * alpha + jnp.sum(p * v, axis=0)
            m_scr[g] = m_cur

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_attention(q, k_pages, v_pages, block_tables, lens, window, *,
                          interpret: bool = True):
    """q: (B, 1, H, D);  k_pages/v_pages: (P, ps, K, D);
    block_tables: (B, M) int32 page ids;  lens: (B,) int32 — valid cache
    entries per slot INCLUDING the just-written token (0 = idle slot);
    window: scalar int32 sliding window (use layers.BIG_WINDOW for none).

    Returns (B, 1, H, D).  Positions are implicit: entry ``o`` of logical
    block ``j`` holds absolute position ``j * ps + o``.
    """
    B, _, H, D = q.shape
    _, ps, K, _ = k_pages.shape
    M = block_tables.shape[1]
    G = H // K
    # query head h = kv_head * G + g  ->  (B, G, K, D), group-major
    qg = q.reshape(B, K, G, D).transpose(0, 2, 1, 3)

    def page_idx(b, j, bt, ln, w):
        # pages past the live length repeat the last live page's index, so
        # the pipeline issues no DMA for them (their compute is skipped)
        last = jnp.maximum(ln[b] - 1, 0) // ps
        return (bt[b, jnp.minimum(j, last)], 0, 0, 0)

    kernel = functools.partial(_paged_kernel, ps=ps, groups=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, G, K, D), lambda b, j, bt, ln, w: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, K, D), page_idx),
            pl.BlockSpec((1, ps, K, D), page_idx),
        ],
        out_specs=pl.BlockSpec((1, G, K, D),
                               lambda b, j, bt, ln, w: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, K, 1), jnp.float32),
            pltpu.VMEM((G, K, 1), jnp.float32),
            pltpu.VMEM((G, K, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, K, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1),
      qg, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, H, D)
