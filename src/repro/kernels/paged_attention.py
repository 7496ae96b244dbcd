"""Decode-mode (Sq=1) flash attention over a paged KV cache — the serving
engine's hot kernel.

The KV cache lives in fixed-size pages shared by all requests, one pool for
every layer: ``(L, n_pages, page_size, K * D)``, the K kv heads of a cache
entry side by side in one row.  Each request owns an ordered list of page
ids (its *block table*).  The kernel never materializes a request's
contiguous KV: the grid's inner axis walks the block table and the
BlockSpec index_map — fed by scalar-prefetched block tables and the layer
index (``pltpu.PrefetchScalarGridSpec``) — DMAs the right physical page of
the right layer for each logical block.  Online softmax accumulates in VMEM
scratch exactly like the prefill kernel in ``flash_attention.py``.

Grid: ``(batch_slots, max_pages_per_seq)``.  One grid step moves one whole
page ``(page_size, K * D)`` for ALL kv heads.  The row of K * D entries is
the pool's minor dimension, so the page is a dense tile wherever K * D is a
multiple of 128 (and a block equal to the full minor dimension elsewhere),
and the pool keeps the row-major layout the model's in-place writes use.
The heads of a row are told apart by a head-indicator mask (lane c belongs
to kv head c // D) on the MXU: a query row spread over the K heads it
belongs to, ``Qm (K, K * D)``, scores a page as ``Qm @ page^T -> (K, ps)``,
and the weights come back as ``p (K, ps) @ page -> (K, K * D)``, whose
row k is read only at head k's lanes.  Both contractions run at
``precision=HIGHEST`` with f32 accumulation, so the scores and the softmax
weights keep the f32 precision of an elementwise reduction.  GQA needs no
KV repeat: the wrapper regroups the query heads as ``(G, K * D)``
(group-major), one ``Qm`` per group.  Pages entirely past a request's
length are skipped with ``pl.when`` and their index_map repeats the last
live page, so no DMA is issued for them (an idle slot with ``len == 0``
skips every page and returns zeros).

The sliding window and the layer arrive as scalar-prefetch operands rather
than static kernel parameters because both are traced values inside the
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
HIGHEST = jax.lax.Precision.HIGHEST


def _head_mask(K: int, D: int):
    """(K, K * D) bool: row k is True at the lanes of kv head k."""
    head = jax.lax.broadcasted_iota(jnp.int32, (K, K * D), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, K * D), 1)
    return (lane >= head * D) & (lane < head * D + D)


def _paged_kernel(bt_ref, len_ref, win_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, qm_scr, m_scr, l_scr, acc_scr, *, ps: int,
                  groups: int, heads: int, hd: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    nt = (((1,), (1,)), ((), ()))               # contract both minor dims

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        heads_of = _head_mask(heads, hd)
        for g in range(groups):
            q = q_ref[0, pl.ds(g, 1), :].astype(jnp.float32)  # (1, K*D)
            qm_scr[g] = jnp.where(heads_of, q, 0.0).astype(qm_scr.dtype)

    qpos = len_ref[b] - 1                       # position of the new token

    # skip pages entirely past the sequence (and everything for idle slots)
    @pl.when(j * ps <= qpos)
    def _compute():
        k = k_ref[0, 0].astype(qm_scr.dtype)                # (ps, K*D)
        v = v_ref[0, 0].astype(jnp.float32)                 # (ps, K*D)
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (heads, ps), 1)
        mask = (kpos <= qpos) & (qpos - kpos < win_ref[0])
        inv = 1.0 / math.sqrt(hd)
        # bf16 operands multiply exactly into the f32 accumulator; f32 ones
        # need the multi-pass HIGHEST contraction to keep f32 precision
        prec = HIGHEST if qm_scr.dtype == jnp.float32 else None
        for g in range(groups):
            s = jax.lax.dot_general(qm_scr[g], k, nt, precision=prec,
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * inv, NEG_INF)           # (K, ps)
            m_prev = m_scr[g]                               # (K, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)                          # (K, ps)
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jnp.dot(
                p, v, precision=HIGHEST,
                preferred_element_type=jnp.float32)         # (K, K*D)
            m_scr[g] = m_cur

    @pl.when(j == nj - 1)
    def _done():
        heads_of = _head_mask(heads, hd)
        for g in range(groups):
            w = acc_scr[g] / jnp.maximum(l_scr[g], 1e-30)   # (K, K*D)
            o_ref[0, pl.ds(g, 1), :] = jnp.sum(
                jnp.where(heads_of, w, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_attention(q, k_pages, v_pages, layer, block_tables, lens,
                          window, *, interpret: bool = True):
    """q: (B, 1, H, D);  k_pages/v_pages: (L, P, ps, K * D);  layer: scalar
    int32, the pool's layer to read;  block_tables: (B, M) int32 page ids;
    lens: (B,) int32 — valid cache entries per slot INCLUDING the
    just-written token (0 = idle slot);  window: scalar int32 sliding window
    (use layers.BIG_WINDOW for none).

    Returns (B, 1, H, D).  Positions are implicit: entry ``o`` of logical
    block ``j`` holds absolute position ``j * ps + o``.
    """
    B, _, H, D = q.shape
    _, _, ps, KD = k_pages.shape
    K = KD // D
    M = block_tables.shape[1]
    G = H // K
    # query head h = kv_head * G + g  ->  (B, G, K * D), group-major
    qg = q.reshape(B, K, G, D).transpose(0, 2, 1, 3).reshape(B, G, KD)
    # the scores' operand type: both bf16 multiply exactly on the MXU
    qm_dtype = jnp.promote_types(q.dtype, k_pages.dtype)

    def page_idx(b, j, bt, ln, w, lyr):
        # pages past the live length repeat the last live page's index, so
        # the pipeline issues no DMA for them (their compute is skipped)
        last = jnp.maximum(ln[b] - 1, 0) // ps
        return (lyr[0], bt[b, jnp.minimum(j, last)], 0, 0)

    kernel = functools.partial(_paged_kernel, ps=ps, groups=G, heads=K,
                               hd=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, G, KD), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, ps, KD), page_idx),
            pl.BlockSpec((1, 1, ps, KD), page_idx),
        ],
        out_specs=pl.BlockSpec((1, G, KD), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, K, KD), qm_dtype),
            pltpu.VMEM((G, K, 1), jnp.float32),
            pltpu.VMEM((G, K, 1), jnp.float32),
            pltpu.VMEM((G, K, KD), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, KD), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1),
      qg, k_pages, v_pages)
    return out.reshape(B, G, K, D).transpose(0, 2, 1, 3).reshape(B, 1, H, D)
