"""Operations and bytes the algorithms need, from shapes alone.

FLOPs count multiply-adds as two.  Model FLOPs: recomputation, padding of
prompts to compile buckets and of the vocabulary are not counted.
"""
from __future__ import annotations

import math

from bench import model


def _lora(m: dict) -> int:
    """LoRA weights one token meets over all layers (both factors)."""
    r = m["lora_rank"]
    return sum(n * r * (i + o)
               for n, i, o in model.backbone(m).lora_dims(m).values())


def causal_pairs(S: int) -> int:
    """(query, key) pairs a causal sequence of length S scores."""
    return S * (S + 1) // 2


def forward_flops(m: dict, n_tok: int, pairs: int, logit_pos: int,
                  lora: bool = True) -> int:
    """One forward pass over ``n_tok`` tokens with ``pairs`` attention
    score pairs and logits at ``logit_pos`` positions."""
    ref = model.backbone(m)
    w = ref.matmul_weights(m) + (_lora(m) if lora else 0)
    return (2 * n_tok * w + pairs * ref.pair_flops(m)
            + 2 * logit_pos * m["d_model"] * m["vocab_size"])


def train_flops(m: dict, B: int, S: int) -> int:
    """Forward, backward to the activations, and the LoRA gradients, of a
    batch of B causal sequences of length S (logits at every position)."""
    n, pairs = B * S, B * causal_pairs(S)
    fwd = forward_flops(m, n, pairs, n)
    attn = pairs * model.backbone(m).pair_flops(m)
    bwd = fwd + attn                   # attention backward is 4 matmuls
    return fwd + bwd + 2 * n * _lora(m)


def round_flops(conf: dict, job: dict) -> int:
    """One ML-ECS round: every client's CCL and AMT steps, and the SE-CCL
    steps (the LLM and the server SLM, each with the soft prompt for its
    own loss and without it for the logit transfer)."""
    slm, llm = conf["clients"]["model"], conf["server_llm"]
    B, S = job["batch_size"], job["seq_len"]
    P = slm["n_soft_tokens"]
    steps = job["local_steps_ccl"] + job["local_steps_amt"]
    clients = conf["clients"]["n"] * steps * train_flops(slm, B, P + S)
    se = job["server_steps"] * (
        train_flops(llm, B, llm["n_soft_tokens"] + S) + train_flops(llm, B, S)
        + train_flops(slm, B, P + S) + train_flops(slm, B, S))
    return clients + se


def prefill_flops(m: dict, n: int) -> int:
    """B=1 prefill of n positions (soft prompt included), logits at the
    last; LoRA merged."""
    return forward_flops(m, n, causal_pairs(n), 1, lora=False)


def decode_flops(m: dict, ctx: int) -> int:
    """One decoded token attending to ``ctx`` cached positions (itself
    included); LoRA merged."""
    return forward_flops(m, 1, ctx, 1, lora=False)


def live_kv_bytes(m: dict, lens, page_size: int, itemsize: int = 2) -> int:
    """Bytes one paged decode step must move for active slots holding
    ``lens`` cached entries (the new token included)."""
    return model.backbone(m).live_kv_bytes(m, lens, page_size, itemsize)


def kv_layers(m: dict) -> int:
    """Layers that keep a paged K/V cache: paged-attention calls a step."""
    return model.backbone(m).kv_layers(m)


def codec_bytes(conf: dict, job: dict) -> int:
    """Bytes the quantize / dequantize kernels need in one round: every
    client's upload quantized once and dequantized twice (error feedback
    and the server's decode), the delivery quantized and dequantized once.
    A tile row is ``block`` values: f32 in, int8 codes and one f32 scale
    out, and back."""
    chan = job.get("channel")
    if not chan:
        return 0
    m = conf["clients"]["model"]
    block, r = chan.get("block", 128), m["lora_rank"]
    rows = 0
    for n, i, o in model.backbone(m).lora_dims(m).values():
        rows += math.ceil(n * i * r / block) + math.ceil(n * r * o / block)
    q = rows * (block * 4 + block + 4)      # f32 in, int8 + scale out
    dq = rows * (block + 4 + block * 4)     # int8 + scale in, f32 out
    n = conf["clients"]["n"]
    up_dq = 2 if chan.get("error_feedback", True) else 1
    return n * (q + up_dq * dq) + q + dq
