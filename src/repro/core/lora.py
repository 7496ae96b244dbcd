"""LoRA parameter handling (paper Eq. 1-2) and trainable/frozen partitioning.

The trainable subtree is extracted as a *flat dict* keyed by '/'-joined
paths.  ``jax.grad`` is taken over that flat dict only, so the gradient
all-reduce in the SPMD train step touches exactly the communicated volume the
paper claims (LoRA + connector ≈ 0.65 % of parameters) — the collective term
of the roofline measures this directly.

For the vectorized federated engine the per-client flat-dicts are stacked
along a leading ``device`` axis (:class:`StackedClients`), so one
``jax.vmap``-ed step replaces the O(N) host loop and MMA aggregation becomes
a single weighted contraction over that axis.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def is_lora_leaf(path: str) -> bool:
    return "_lora_a" in path or "_lora_b" in path


def default_trainable(path: str) -> bool:
    """The paper's AMT trainable set: LoRA adapters + the multimodal
    connector + the (stub) frontend projector."""
    return (is_lora_leaf(path) or path.startswith("connector")
            or path.startswith("frontend"))


def all_trainable(path: str) -> bool:
    """Full fine-tune (the Multi-FedAvg baseline)."""
    return True


def partition(params, predicate: Callable[[str], bool] = default_trainable
              ) -> Dict[str, jnp.ndarray]:
    """Extract the trainable leaves as a flat {path: leaf} dict."""
    out = {}

    def visit(path, leaf):
        s = path_str(path)
        if predicate(s):
            out[s] = leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return out


def combine(params, trainable: Dict[str, jnp.ndarray]):
    """Re-insert trainable leaves into the full parameter tree.

    ``trainable`` may be a *partial* dict (e.g. a heterogeneous cohort's
    shared-subset delivery): leaves without an entry pass through
    untouched.
    """
    def pick(path, leaf):
        return trainable.get(path_str(path), leaf)
    return jax.tree_util.tree_map_with_path(pick, params)


def shared_keys(a: Dict[str, jnp.ndarray], b: Dict[str, jnp.ndarray]
                ) -> Tuple[str, ...]:
    """Keys present in BOTH flat dicts with identical shape and dtype —
    the cross-architecture exchange subset of the cohort API (aggregating
    mismatched shapes is undefined; mismatched keys stay cohort-local)."""
    return tuple(sorted(
        k for k, v in a.items()
        if k in b and b[k].shape == v.shape and b[k].dtype == v.dtype))


# ---------------------------------------------------------------------------
# device-stacked client state (the vectorized federated engine)

def stack_trees(trees: Sequence):
    """Stack identically-structured pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(tree, n: int) -> List:
    """Inverse of :func:`stack_trees` — n pytrees without the leading axis."""
    return [gather_tree_device(tree, j) for j in range(n)]


def gather_tree_device(tree, j: int):
    """Slice device ``j`` out of a stacked pytree (leading axis indexed)."""
    return jax.tree.map(lambda x: x[j], tree)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedClients:
    """Every client's trainable flat-dict stacked on a leading device axis.

    ``trainable`` maps '/'-joined paths to arrays of shape ``(N, ...)`` —
    the per-client leaf shapes with one extra leading ``device`` dim.  This
    is the unit the vectorized federated engine vmaps local steps over and
    the unit MMA aggregation contracts; it is a registered pytree so it can
    flow straight through ``jax.jit`` / ``jax.vmap`` boundaries.
    """

    trainable: Dict[str, jnp.ndarray]

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        keys = sorted(self.trainable)
        return [self.trainable[k] for k in keys], keys

    @classmethod
    def tree_unflatten(cls, keys, leaves):
        return cls(dict(zip(keys, leaves)))

    # -- construction / views ---------------------------------------------
    @property
    def n_devices(self) -> int:
        leaf = next(iter(self.trainable.values()))
        return leaf.shape[0]

    @classmethod
    def stack(cls, clients: Sequence[Dict[str, jnp.ndarray]]
              ) -> "StackedClients":
        """Stack per-client flat dicts (identical key sets) device-major."""
        assert clients, "need at least one client"
        keys = set(clients[0])
        assert all(set(c) == keys for c in clients), "client key mismatch"
        return cls({k: jnp.stack([c[k] for c in clients])
                    for k in clients[0]})

    def unstack(self) -> List[Dict[str, jnp.ndarray]]:
        return [self.gather_device(j) for j in range(self.n_devices)]

    def gather_device(self, j: int) -> Dict[str, jnp.ndarray]:
        return {k: v[j] for k, v in self.trainable.items()}

    def broadcast(self, flat: Dict[str, jnp.ndarray]) -> "StackedClients":
        """Replace every device's entry with a shared flat-dict (the
        redistribution step, Alg. 1 line 5) — zero-copy broadcast."""
        n = self.n_devices
        return StackedClients({
            k: jnp.broadcast_to(flat[k], (n,) + flat[k].shape)
            for k in self.trainable})


def n_params(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def communicated_fraction(params,
                          predicate: Callable[[str], bool] = is_lora_leaf,
                          channel=None) -> float:
    """Fraction of total parameter volume communicated per round (paper
    Fig. 3: 0.65 % for the r=8 SLM).

    With ``channel=None`` this is the historical *count* fraction
    (communicated parameters / total parameters).  Pass a
    :class:`repro.core.channel.Channel` (or ``ChannelSpec``) and it
    becomes a *byte* fraction instead: the codec's exact
    ``bytes_on_wire`` for the communicated leaves over the dense byte
    size of the full model — so an int8 channel reports roughly a
    quarter of the f32 identity figure, matching the engines'
    ``comm_stats`` accounting.
    """
    flat = partition(params, lambda p: predicate(p))
    if channel is None:
        return n_params(flat) / max(1, n_params(params))
    channel = channel.make() if hasattr(channel, "make") else channel
    # leaves may be arrays OR eval_shape ShapeDtypeStructs — touch only
    # .shape/.dtype so the abstract (no-weights) benchmark path works
    like = {k: jax.ShapeDtypeStruct((1,) + tuple(v.shape), v.dtype)
            for k, v in flat.items()}
    total = sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                for x in jax.tree.leaves(params))
    return channel.bytes_on_wire(like) / max(1, total)


def merge_lora(params, cfg):
    """Fold LoRA updates into the frozen weights (W' = W + (α/r) BA) and
    drop the adapter leaves — used before serving so decode pays no
    adapter cost (and applies each update exactly once: a merged weight
    next to its adapter would apply BA twice)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    by_path = {path_str(p): (p, leaf) for p, leaf in flat}
    scale = cfg.lora_alpha / cfg.lora_rank
    new = {}
    for s, (p, leaf) in by_path.items():
        if is_lora_leaf(s):
            continue
        a_key, b_key = s + "_lora_a", s + "_lora_b"
        if a_key in by_path:
            a = by_path[a_key][1]
            b = by_path[b_key][1]
            leaf = (leaf.astype(jnp.float32)
                    + scale * (a.astype(jnp.float32)
                               @ b.astype(jnp.float32))).astype(leaf.dtype)
        new[s] = leaf
    return _drop_lora(jax.tree_util.tree_unflatten(
        treedef, [new.get(path_str(p)) for p, _ in flat]))


def _drop_lora(tree):
    """``tree`` without its adapter entries (nested dicts/lists)."""
    if isinstance(tree, dict):
        return {k: _drop_lora(v) for k, v in tree.items()
                if not is_lora_leaf(str(k))}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_drop_lora(v) for v in tree)
    return tree
