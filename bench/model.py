"""What the benchmark's references share, whatever the backbone: the
weights, the ML-ECS parts and the precision of the plain reference.

Everything here is written from the published description and imports
nothing of the program under test:

- ``backbone``: the reference module of a model's backbone, named by the
  model's ``reference`` key (``bench/references/dense.py`` without it;
  that file says what a reference module defines);
- ``draw_model``: flat weights in the program's layout ('/'-joined paths),
  drawn from a key on the device, in the configuration's ``dtype``;
- the matmuls of the reference in float32 at ``Precision.HIGHEST``, and
  with ``prec="fp8"`` on operands rounded to float8 e4m3 first: the
  lower-precision control;
- the LoRA adapters and the multimodal connector (per-modality projectors,
  fusion MLP, soft-prompt generator) that every client trains, the
  Gram-volume contrastive loss and the next-token loss of the ML-ECS paper.

A model is described by a dict of sizes (the configuration files under
``bench/configs``), with the program's own key names.
"""
from __future__ import annotations

import math
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import common

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
DEFAULT_REFERENCE = "bench/references/dense.py"


def backbone(m: dict):
    """The reference module named by ``m["reference"]`` (a path from the
    checkout's root to a file under ``bench/``), loaded once a process."""
    rel = m.get("reference", DEFAULT_REFERENCE)
    path = os.path.realpath(os.path.join(common.ROOT, rel))
    if not path.startswith(os.path.realpath(common.BENCH_DIR) + os.sep):
        raise ValueError(f"reference {rel!r} is not a file under bench/")
    return common.load_file(path)


def padded_vocab(m: dict) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 256.
    The rows past ``vocab_size`` are zero, so they never win an argmax."""
    return ((m["vocab_size"] + 255) // 256) * 256


def latent(m: dict) -> int:
    return m.get("connector_dim") or m["d_model"]


# ---------------------------------------------------------------------------
# weights

def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _normal(key, path, shape, std, dtype):
    return (jax.random.normal(_leaf_key(key, path), shape, F32) * std
            ).astype(dtype)


def personal_shapes(m: dict, lora_b_std: float) -> dict:
    """{path: (shape, std)} of the trainable leaves: LoRA on the
    projections the backbone's ``lora_dims`` names and the multimodal
    connector."""
    d, r = m["d_model"], m["lora_rank"]
    s = {}
    for base, (n, i, o) in backbone(m).lora_dims(m).items():
        s[f"{base}_lora_a"] = ((n, i, r), 1.0 / math.sqrt(i))
        s[f"{base}_lora_b"] = ((n, r, o), lora_b_std or None)
    if m.get("n_modalities", 0) > 0:
        M, fd, c, n = (m["n_modalities"], m["modality_dim"], latent(m),
                       m["n_soft_tokens"])
        s.update({
            "connector/proj_w": ((M, fd, c), 1.0 / math.sqrt(fd)),
            "connector/proj_b": ((M, c), None),
            "connector/fuse_w1": ((M * c, c), 1.0 / math.sqrt(M * c)),
            "connector/fuse_w2": ((c, c), 1.0 / math.sqrt(c)),
            "connector/spg_w1": ((c, d), 1.0 / math.sqrt(c)),
            "connector/spg_scale": ((n, d), "ones"),
            "connector/spg_bias": ((n, d), 0.02)})
    return s


def _draw(key, shapes: dict, dtype, real_vocab: int = 0) -> dict:
    out = {}
    for path, (shape, std) in shapes.items():
        if std is None:
            out[path] = jnp.zeros(shape, dtype)
        elif std == "ones":
            out[path] = jnp.ones(shape, dtype)
        else:
            out[path] = _normal(key, path, shape, std, dtype)
    if real_vocab and "tok/embed" in out:
        rows = jnp.arange(out["tok/embed"].shape[0])[:, None] < real_vocab
        out["tok/embed"] = jnp.where(rows, out["tok/embed"], 0)
    return out


def nest(flat: dict) -> dict:
    """'/'-joined paths -> nested dicts."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts -> {'/'-joined path: leaf}."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def draw_model(key, m: dict, lora_b_std: float = 0.0):
    """Flat weights of one model, backbone and trainable leaves, from
    ``key``, in the model's ``dtype``."""
    dt = jnp.dtype(m["dtype"])
    flat = _draw(key, backbone(m).backbone_shapes(m), dt, m["vocab_size"])
    flat.update(_draw(key, personal_shapes(m, lora_b_std), dt))
    return flat


def is_trainable(path: str) -> bool:
    return "_lora_" in path or path.startswith("connector/")


def is_lora(path: str) -> bool:
    return "_lora_" in path


# ---------------------------------------------------------------------------
# the reference's precision and the pieces of its layers

def _q8(x):
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _cast(x, prec):
    x = x.astype(F32)
    return _q8(x) if prec == "fp8" else x


def mm(a, b, prec="f32"):
    return jnp.matmul(_cast(a, prec), _cast(b, prec), precision=HI,
                      preferred_element_type=F32)


def ein(spec, a, b, prec="f32"):
    return jnp.einsum(spec, _cast(a, prec), _cast(b, prec), precision=HI,
                      preferred_element_type=F32)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale.astype(F32))


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# multimodal connector and the Gram-volume contrastive loss

def connector(cp, m: dict, feats, mask, prec="f32"):
    """(soft prompt (B, n, d), modality reps (B, M, c), fused (B, c))."""
    mk = mask.astype(F32)[..., None]
    h = (ein("bmf,mfd->bmd", feats, cp["proj_w"], prec)
         + cp["proj_b"].astype(F32)) * mk
    B = h.shape[0]
    fused = mm(gelu(mm((h * mk).reshape(B, -1), cp["fuse_w1"], prec)),
               cp["fuse_w2"], prec)
    g = gelu(mm(fused, cp["spg_w1"], prec))
    soft = g[:, None, :] * cp["spg_scale"].astype(F32)[None] \
        + cp["spg_bias"].astype(F32)[None]
    return soft, h, fused


def _log_volume(vs, mask):
    sq = jnp.sum(vs * vs, -1, keepdims=True)
    v = vs * jax.lax.rsqrt(sq + 1e-12)
    g = jnp.einsum("...kd,...ld->...kl", v, v, precision=HI)
    k = vs.shape[-2]
    both = mask[..., :, None] & mask[..., None, :]
    g = jnp.where(both, g, jnp.eye(k, dtype=F32)) + 1e-5 * jnp.eye(k)
    return jnp.sum(jnp.log(jnp.diagonal(jnp.linalg.cholesky(g), 0, -2, -1)),
                   -1)


def contrastive(anchor, mods, mask, n_negatives: int):
    """½(O2A + A2O): InfoNCE over negated Gram volumes of the anchor with
    each sample's modality set, negatives by rolling the batch."""
    B = anchor.shape[0]
    U = max(1, min(n_negatives, B - 1))
    ones = jnp.ones((B, 1), bool)

    def vol(a, ms, mk):
        return _log_volume(jnp.concatenate([a[:, None], ms], 1),
                           jnp.concatenate([ones, mk], 1))

    def side(roll_mods):
        cols = [vol(anchor, mods, mask)]
        for u in range(1, U + 1):
            if roll_mods:
                cols.append(vol(anchor, jnp.roll(mods, u, 0),
                                jnp.roll(mask, u, 0)))
            else:
                cols.append(vol(jnp.roll(anchor, u, 0), mods, mask))
        return -jax.nn.log_softmax(-jnp.stack(cols, -1), -1)[:, 0]
    return 0.5 * (jnp.mean(side(True)) + jnp.mean(side(False)))


def lm_ce(logits, tokens, loss_mask):
    """Mean next-token cross-entropy over the masked positions after the
    prefix (logits cover prefix + tokens)."""
    S = tokens.shape[1]
    P = logits.shape[1] - S
    lp = jax.nn.log_softmax(logits[:, P:P + S - 1], -1)
    nll = -jnp.take_along_axis(lp, tokens[:, 1:, None], -1)[..., 0]
    w = loss_mask[:, 1:].astype(F32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def np_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed derived from the run's ``--seed`` and a salt."""
    return int(np.random.SeedSequence([seed, *salt]).generate_state(1)[0]
               & 0x7FFFFFFF)
