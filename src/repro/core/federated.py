"""The ML-ECS federated orchestrator — Algorithm 1 end to end, three
engines, cohort-structured federations.

One cloud server (unified LLM model + a server-side SLM) and N edge devices
(unified SLM models with heterogeneous modality availability).  Per round t:

  1. server generates fused omni-modal anchors s'(t) on the public dataset;
  2. each device runs CCL (public data, anchored) then AMT (private data),
     then uploads the LoRA params of its SLM backbone;
  3. server aggregates uploads with MMA weights (Eq. 13) into its SLM;
  4. server runs SE-CCL — bidirectional pooled-KL transfer between its SLM
     and LLM on the public data (Eq. 15-16);
  5. the server SLM's LoRA params are redistributed to every device.

**Cohorts (model-structure heterogeneity).**  The runner is built from a
:class:`repro.core.spec.FederationSpec`: an ordered tuple of
:class:`~repro.core.spec.ClientCohort`\\ s, each holding ``n_clients``
devices that share ONE architecture (plus an optional modality subset,
per-cohort MER ``rho`` and data fraction).  Intra-cohort homogeneity is the
*documented invariant* that makes a cohort vectorizable — ``jax.vmap``
needs one trace — so each cohort keeps its own device-stacked state and
runs the engines' scan-over-vmap machinery internally.  Across cohorts the
protocol operates on the **shared subset**: the LoRA keys whose path and
shape match the server SLM (all of them in the homogeneous case; under
heterogeneity, e.g. a different ``d_model``, the mismatched adapters
federate within their cohort only, via the intra-cohort MMA average).
Aggregation is two-level but order-deterministic: per-cohort f32 partial
sums under *globally* normalized Eq. 13 weights
(:func:`repro.core.mma.partial_aggregate_stacked`), then a cohort-ordered
shared-key combine (:func:`repro.core.mma.combine_cohort_partials`).  The
legacy constructor ``FederatedRunner(cfg, slm_bundle, llm_bundle, corpus)``
survives as a thin shim over
:meth:`repro.core.spec.FederationSpec.from_legacy` and reproduces the
pre-cohort runner bit-for-bit (single cohort ⇒ every key shared, identical
seeds/streams, identical fused-round computation graph).

Three interchangeable engines drive a round:

* ``engine="loop"`` — the reference host simulation: a Python loop over
  cohorts and their devices with per-cohort jitted steps and host-side
  upload lists.  O(N) dispatch overhead; kept as the numerical ground
  truth.
* ``engine="vectorized"`` (default) — every cohort's client state is
  stacked on a leading ``device`` axis (full params/opt pytrees; trainable
  uploads as :class:`repro.core.lora.StackedClients`) and one *fused,
  jitted* round function runs the whole protocol for ALL cohorts:
  ``lax.scan`` over local steps of each cohort's ``vmap``-ed CCL/AMT step,
  MMA weighting + aggregation as stacked contractions, the cross-cohort
  shared-subset combine, SE-CCL scanned on the server, and redistribution
  as per-cohort broadcasts — uploads never materialize as Python lists.
  Per-device data comes pre-batched from the per-GLOBAL-client stream
  bank (:class:`repro.data.pipeline.ClientStreams` — one shuffle stream
  per registered client), which replays the exact per-device shuffle
  streams of the loop engine, so the engines see identical data and agree
  on round summaries to ~1e-5.
  With a ``mesh``, every cohort's stacked axis is placed on the "data"
  mesh axis (``NamedSharding``) so clients parallelize across chips; on
  the single-device host mesh the placement is a no-op and results are
  exact.
* ``engine="overlap"`` — the round split into per-cohort jitted *device
  phases* (CCL/AMT scan + the cohort's MMA partial = the upload) and a
  jitted *server phase* (shared-subset landing + SE-CCL scan + the
  redistribution payload) software-pipelined across rounds.  The server
  chain lives on the last local device when more than one exists, so round
  *r*'s SE-CCL training runs concurrently with round *r+1*'s device scans;
  host batch assembly is double-buffered by
  :class:`repro.data.pipeline.RoundPrefetcher`.  ``cfg.staleness`` sets
  how many rounds the redistributed LoRA (and the CCL anchor model) may
  lag: ``staleness=0`` reproduces the vectorized engine's schedule
  exactly, ``staleness=1`` feeds device phase *r+1* the server outputs of
  round *r-1* — taking the server phase off the critical path entirely;
  deeper staleness pipelines further (redistribution skips the ``s``
  warm-up rounds).  ``mesh`` may also be a *per-cohort list* of meshes
  (see :func:`repro.launch.mesh.make_cohort_meshes`): each cohort's stack
  then shards over its own disjoint device slice, so differently-shaped
  cohort scans — which cannot share one ``vmap`` — execute concurrently on
  disjoint hardware via async dispatch.  Only the shared LoRA subset ever
  crosses the edge-cloud boundary (the paper's 0.65 % communication
  volume).

Evaluation follows the same engine contract.  All engines share ONE metric
definition (:func:`repro.core.seccl.make_eval_step`: masked token CE +
template accuracy, padding rows weighted exactly zero).  The loop engine
drives the jitted per-batch step from a host loop over
:func:`repro.data.pipeline.eval_batches` — the reference.  The stacked
engines precompute padded device-stacked eval shards per cohort
(:func:`repro.data.pipeline.stacked_eval_batches`, constant across rounds)
and compute each cohort's client metrics in one jitted scan-over-``vmap``
call, plus the N-independent SE-CCL server evaluation as one jitted scan.
Round metrics list clients in global order (cohorts are contiguous index
ranges), so single-cohort outputs are byte-identical to the legacy runner.

**Registered population vs per-round working set.**  A
:class:`~repro.core.spec.ParticipantSampler` on the spec splits client
state into two layers: the full population's personal state (trainable
LoRA/connector leaves + optimizer moments) lives host/disk-side in a
:class:`repro.core.store.ClientStore`, while the engines keep only a
FIXED-size stacked working set on device.  Each round,
:class:`repro.core.store.ParticipantSchedule` draws the participants
(stateless replay from ``(seed, round)``, like the fault schedule), the
runner *gathers* their rows from the store into the stacked buffers (the
shared frozen backbone never moves), runs the unchanged jitted round
machinery on Eq. 13 weights renormalized over the sampled set
(:func:`repro.core.mma.sampled_weights` — composing with the fault
model's survivor renormalization), and *scatters* the trained rows back.
Membership enters jit as DATA (gather indices, weight vectors, masks),
never as shapes — resampling adds zero recompilations after warm-up
(assert via :meth:`FederatedRunner.jit_cache_sizes`) — and device memory
scales with the working set, not the registered N.  The overlap engine
additionally stages round r+1's store gather on a background thread.  A
sampler covering the full population reproduces the unsampled engines
bit-for-bit.  :meth:`FederatedRunner.save_checkpoint` /
:meth:`~FederatedRunner.load_checkpoint` round-trip the whole run state
(round counter, server, population) through
:class:`repro.checkpointing.CheckpointManager`; restore replays sampler
draws and data-stream positions from the round counter alone, so resumed
rounds are bit-identical to the uninterrupted run.

Every tree that crosses the edge-cloud boundary — client uploads on
every engine, the downlink redistribution — routes through ONE wire
contract, :class:`repro.core.channel.Channel` (``channel=`` on the
spec).  The identity codec is a literal pass-through (channel-less
behaviour, bit-exact); quantized/sketched codecs encode inside the
device phase (Pallas kernels on TPU), decode at the phase boundary
before any reduction (order statistics need dense per-client values),
carry per-client error-feedback residuals as client state (stacked
``rt.chan_state`` or the store entries' ``"chan"`` key), and report
exact measured traffic via :attr:`FederatedRunner.comm_stats`.  Codec
state is jit DATA like membership — no codec, fault or sampling round
retraces after warm-up.

Ablation switches (use_mma / use_seccl / use_ccl) give the paper's Fig. 4
variants; ``baseline`` selects Standalone / Multi-FedAvg comparisons.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ccl as ccl_lib
from repro.core import lora, mma, seccl
from repro.core.channel import Channel, ChannelSpec
from repro.core.faults import FaultSchedule
from repro.core.spec import (CCL_SCORES, ENGINES, MODES, ClientCohort,
                             FaultSpec, FederationSpec, ParticipantSampler,
                             validate_protocol)
from repro.core.store import ClientStore, ParticipantSchedule
from repro.data import attacks
from repro.data.multimodal import paper_split, take_fraction, train_test_split
from repro.data.pipeline import (ClientStreams, RoundPrefetcher, eval_batches,
                                 np_eval_batches, stack_eval_steps,
                                 stacked_eval_batches)
from repro.models.model import ModelBundle, build_model
from repro.optim.adamw import adamw, apply_updates
from repro.sharding import partition as shard_part
from repro.sharding.rules import TRAIN_RULES

# Host spans of a round, ``fed.<step>`` with ``round=<n>`` (and ``cohort=<c>``
# where per cohort), written into a running profiler trace on the device
# trace's clock; without a trace each costs about a microsecond.  The traced
# functions name their layers with ``jax.named_scope`` (``device_phase`` ⊃
# ``ccl``/``amt``, ``channel``, ``mma``, ``server_phase``, ``redistribute``),
# which reaches the device ops' metadata only.
_span = jax.profiler.TraceAnnotation


# Shared protocol-gating predicates.  Every engine MUST gate the same phase
# on the same predicate — a bare ``cfg.use_seccl`` in one engine and
# ``mode not in (...) and cfg.use_seccl`` in another silently diverges the
# moment a new mode is added (the PR 4 engine-parity bugfix).  Mode strings
# themselves are validated at config construction (spec.validate_protocol),
# so an unknown mode can no longer slip through these gates.

def _do_ccl(cfg: "FederatedConfig") -> bool:
    """Does the device phase run the CCL (public-data, anchored) steps?"""
    return cfg.mode != "standalone" and cfg.use_ccl


def _do_seccl(cfg: "FederatedConfig") -> bool:
    """Does the server run the SE-CCL training phase (Alg. 1 step 4)?"""
    return cfg.mode not in ("standalone", "fedavg") and cfg.use_seccl


def _ccl_weight(cfg: "FederatedConfig") -> float:
    """CCL loss weight of the device public-data steps (0 outside mlecs)."""
    return 0.5 if (cfg.use_ccl and cfg.mode == "mlecs") else 0.0


def _where_clients(mask, new, old):
    """Per-client select over the stacked leading axis: ``new`` where the
    client participated this round, ``old`` (its pre-round value) where it
    was offline.  The dropout "freeze" as pure data flow — the mask is a
    traced (n,) vector, so fault rounds share the clean round's compiled
    trace instead of changing any shape."""
    def sel(a, b):
        m = mask.reshape(mask.shape[:1] + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(sel, new, old)


def _scale_uploads(uploads: "lora.StackedClients", scale):
    """Byzantine scaled-update inside the compiled round: each client
    REPORTS ``scale_j × u_j`` (1.0 for honest clients) while its local
    params stay honest — the in-jit vector form of
    :func:`repro.data.attacks.scaled_update`."""
    return lora.StackedClients(
        {k: (v.astype(jnp.float32)
             * scale.reshape(scale.shape[:1] + (1,) * (v.ndim - 1))
             ).astype(v.dtype)
         for k, v in uploads.trainable.items()})


@dataclasses.dataclass
class FederatedConfig:
    """Hyperparameters of one federated simulation (the legacy flat view;
    :class:`repro.core.spec.FederationSpec` is the cohort-aware superset).

    ``engine`` picks the round implementation ("vectorized" fused-jit
    default, "loop" sequential reference, "overlap" pipelined phases with
    ``staleness`` rounds of server lag); the ablation flags (``use_mma``,
    ``use_seccl``, ``use_ccl``) and ``mode`` select the paper's Fig. 4 /
    baseline variants.  ``rho`` is the MER modality-existing rate drawn per
    device; ``kt_weight`` scales the SE-CCL bidirectional KT terms.
    Unknown ``mode`` / ``engine`` / ``ccl_score`` strings and
    ``staleness > 0`` outside the overlap engine are rejected at
    construction.
    """

    n_devices: int = 3
    rounds: int = 5
    local_steps_ccl: int = 4
    local_steps_amt: int = 4
    server_steps: int = 4
    batch_size: int = 8
    lr: float = 3e-3
    rho: float = 0.7                 # modality existing rate (MER)
    n_negatives: int = 4
    seed: int = 0
    engine: str = "vectorized"       # vectorized (fused round) | loop (ref)
                                     # | overlap (pipelined phases)
    staleness: int = 0               # overlap engine: rounds the
                                     # redistributed LoRA / anchor model may
                                     # lag (0 = vectorized schedule; 1 =
                                     # server phase off the critical path)
    # ablations / baselines
    use_mma: bool = True             # False -> uniform averaging (w/o MMA)
    use_seccl: bool = True           # False -> skip step 4     (w/o SE-CCL)
    use_ccl: bool = True             # False -> devices skip step 2's loss
    mode: str = "mlecs"              # mlecs | standalone | fedavg
    kt_weight: float = 0.5
    prox_weight: float = 0.0         # FedProx-style pull toward the global
                                     # params (FedMLLM-baseline proxy)
    ccl_score: str = "volume"        # volume (paper Eq. 5-8) | cosine
                                     # (pairwise prior-work ablation)
    robust: str = "mean"             # MMA reduction: mean (Eq. 13) |
                                     # trimmed_mean | norm_clip
    trim_frac: float = 0.2           # trimmed_mean: fraction cut per end
    faults: Optional[FaultSpec] = None   # unreliable-client model (None =
                                     # every client honest and always on)
    sampler: Optional[ParticipantSampler] = None  # per-round participant
                                     # sampling over the registered
                                     # population (None = all clients
                                     # participate every round)
    channel: Optional[ChannelSpec] = None  # wire codec for every
                                     # edge-crossing tree (None = identity,
                                     # bit-exact pre-channel behaviour)

    def __post_init__(self):
        if self.n_devices < 1:
            raise ValueError("n_devices must be >= 1")
        validate_protocol(self.mode, self.engine, self.ccl_score,
                          self.staleness, self.robust, self.trim_frac)


class _Cohort:
    """Runtime state of one cohort: its model bundle, the contiguous
    global-client slice it owns, globally-normalized Eq. 13 weights, the
    server-shape-shared key subset, and the engine-specific client state
    (device-stacked trees or per-client lists).  Internal to
    :class:`FederatedRunner`; exposed read-only via ``runner.cohorts``."""

    def __init__(self, idx: int, spec: ClientCohort, bundle: ModelBundle,
                 offset: int):
        self.idx = idx
        self.spec = spec
        self.bundle = bundle
        self.offset = offset
        self.n = spec.n_clients
        self.weights = None          # (n,) globally-normalized MMA weights
        self.w_total = 0.0           # float(sum(weights)) — cohort mass
        self.shared: Tuple[str, ...] = ()   # server-shape-matching LoRA keys
        self.own: Tuple[str, ...] = ()      # cohort-local LoRA keys
        self.last_global = None      # last delivery (prox/redistribution ref)
        # per-round working set (== the full membership without a sampler):
        # the stacked buffers hold work_n clients, and every per-round
        # vector (weights/presence/scale) is indexed by work_slice
        self.work_n = spec.n_clients
        self.work_offset = offset
        self.eval_cache: Dict = {}   # sampled-eval shards keyed by members

    @property
    def slice(self) -> slice:
        """Global client-index slice of this cohort's members."""
        return slice(self.offset, self.offset + self.n)

    @property
    def work_slice(self) -> slice:
        """This cohort's block of the round's working-set vectors — equal
        to :attr:`slice` without a sampler (working set = population)."""
        return slice(self.work_offset, self.work_offset + self.work_n)


class FederatedRunner:
    """Simulates the edge-cloud environment (the paper's N=3..20 and the
    roadmap's N>>20 sweeps) from a :class:`FederationSpec`:

        ``FederatedRunner(spec, corpus, mesh=..., engine=...)``

    or through the legacy single-cohort shim (bit-for-bit the pre-cohort
    runner):

        ``FederatedRunner(cfg, slm_bundle, llm_bundle, corpus, ...)``

    ``engine`` overrides ``spec.engine``.  ``mesh`` (optional) shards the
    stacked engines' client stacks across chips: a single
    ``jax.sharding.Mesh`` places every cohort on its "data" axis; a
    per-cohort *list* of meshes (overlap engine only — one jit cannot span
    disjoint device sets) gives each cohort its own device slice so
    heterogeneous cohorts run concurrently."""

    def __init__(self, spec, *args, mesh=None, engine: Optional[str] = None,
                 store_dir: Optional[str] = None):
        if isinstance(spec, FederationSpec):
            if not args:
                raise TypeError(
                    "FederatedRunner(spec, corpus, mesh=..., engine=...)")
            corpus, rest = args[0], args[1:]
            bundles = [build_model(c.model) for c in spec.cohorts]
            llm_bundle = build_model(spec.server_llm)
            srv_slm_bundle = (bundles[0] if spec.server_slm is None
                              else build_model(spec.server_slm))
        elif isinstance(spec, FederatedConfig):
            if len(args) < 3:
                raise TypeError("legacy form: FederatedRunner(cfg, "
                                "slm_bundle, llm_bundle, corpus, ...)")
            slm_bundle, llm_bundle, corpus = args[:3]
            rest = args[3:]
            spec = FederationSpec.from_legacy(spec, slm_bundle.cfg,
                                              llm_bundle.cfg)
            bundles = [slm_bundle]
            srv_slm_bundle = slm_bundle
        else:
            raise TypeError(f"expected FederationSpec or FederatedConfig, "
                            f"got {type(spec).__name__}")
        if rest:                     # positional mesh [, engine]
            mesh = rest[0] if mesh is None else mesh
            if len(rest) > 1 and engine is None:
                engine = rest[1]

        self.spec = spec
        self.cfg = cfg = spec.to_config()
        self.engine = engine or spec.engine
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}")
        if cfg.staleness > 0 and self.engine != "overlap":
            raise ValueError("staleness > 0 requires the overlap engine")

        # the wire codec: ONE channel object shared by every edge-crossing
        # path (uplink encode in the engines, downlink multicast, bytes
        # accounting).  identity = the bit-exact pre-channel behaviour.
        self.channel = (spec.channel if spec.channel is not None
                        else ChannelSpec()).make()

        if isinstance(mesh, (list, tuple)):
            if len(mesh) != spec.n_cohorts:
                raise ValueError(
                    f"per-cohort mesh list has {len(mesh)} entries for "
                    f"{spec.n_cohorts} cohorts")
            if self.engine != "overlap":
                raise ValueError(
                    "per-cohort meshes need engine='overlap' — one fused "
                    "jit cannot span disjoint device sets; pass a single "
                    "shared Mesh for the vectorized engine")
            self._meshes: Optional[Tuple] = tuple(mesh)
            self.mesh = None
        else:
            self._meshes = None
            self.mesh = mesh

        self.slm = bundles[0]        # legacy alias: cohort 0's bundle
        self.llm = llm_bundle
        self._srv_slm_bundle = srv_slm_bundle
        N = cfg.n_devices
        key = jax.random.key(cfg.seed)
        keys = jax.random.split(key, N + 2)

        # data: public / private, train / test, modality masks.  Private
        # shards are allocated over the GLOBAL client index (cohort
        # boundaries never change who owns which rows), then optionally
        # thinned by the owning cohort's data_fraction.
        public, privates = paper_split(corpus, N, cfg.seed)
        self.public_train, self.public_test = train_test_split(
            public, 0.1, cfg.seed)
        self.priv_train, self.priv_test = [], []
        for j, pv in enumerate(privates):
            frac = spec.cohorts[spec.cohort_of(j)].data_fraction
            pv = take_fraction(pv, frac, cfg.seed + 10_000 + j)
            tr, te = train_test_split(pv, 0.1, cfg.seed + j + 1)
            self.priv_train.append(tr)
            self.priv_test.append(te)
        M = corpus["modality_feats"].shape[1]
        self.masks = spec.draw_masks(M)

        # client-fault model: the schedule's per-round draws are host data
        # consumed by the compiled rounds as zero-weight masks (never
        # shapes).  Label-flip poisoning rewrites the Byzantine clients'
        # private TRAIN shards here — before any iterator snapshots them —
        # so every engine reads identical (poisoned) shuffle streams; test
        # shards stay clean (degradation is measured on honest data).
        self._faults = (FaultSchedule(spec.faults, N)
                        if spec.faults is not None else None)
        self._round_idx = 0
        self._rnd_present = None     # (S,) bool — training + delivery mask
        self._rnd_contrib = None     # (S,) bool — aggregation mask
        self._rnd_weights = None     # (S,) f32 — survivor-renormalized
        self._attack_scale = None    # (N,) f32 — scaled-update vector
        # participant sampling: the registered population (ClientStore)
        # vs the per-round working set (the stacked buffers).  Per-round
        # vectors above are working-set sized (S == N without a sampler).
        self._schedule = (ParticipantSchedule(
            spec.sampler, [c.n_clients for c in spec.cohorts], spec.offsets)
            if spec.sampler is not None else None)
        self._store = (ClientStore(directory=store_dir)
                       if self._schedule is not None else None)
        self._cohort_bases = None    # frozen base per cohort (sampler only)
        self._rnd_locals = None      # per-cohort sampled LOCAL indices
        self._rnd_ids = None         # (S,) sampled GLOBAL client ids
        self._rnd_no = None          # the round index the draws belong to
        self._rnd_scale = None       # (S,) per-round attack-scale gather
        self._assemble_idx = 0       # rounds assembled (prefetch runs ahead)
        if self._faults is not None:
            fl = spec.faults
            if fl.attack == "label_flip":
                for j in np.flatnonzero(self._faults.byzantine):
                    self.priv_train[j] = attacks.label_flip(
                        self.priv_train[j], seed=fl.seed + 31_000 + j)
            elif fl.attack == "scaled_update" and \
                    bool(self._faults.byzantine.any()):
                self._attack_scale = np.where(
                    self._faults.byzantine, fl.attack_scale,
                    1.0).astype(np.float32)

        # models (per-cohort architectures; global key schedule).  Every
        # cohort member shares ONE frozen backbone — the deployed
        # pretrained architecture, drawn from the cohort's first member
        # key — while each member's personal (trainable: LoRA + connector
        # + frontend) leaves still draw from its own keys[j] stream.  The
        # per-client state that federation moves, stores and checkpoints
        # is therefore exactly the personal subset: a registered
        # population costs one backbone per cohort plus N personal sets,
        # not N full models.
        bases = [ccl_lib.init_unified(keys[spec.offsets[c]], bundles[c])
                 for c in range(spec.n_cohorts)]
        device_params = []
        for j in range(N):
            c = spec.cohort_of(j)
            if j == spec.offsets[c]:
                device_params.append(bases[c])
            else:
                device_params.append(lora.combine(
                    bases[c],
                    lora.partition(ccl_lib.init_unified(keys[j],
                                                        bundles[c]))))
        self.server_llm = ccl_lib.init_unified(keys[-1], self.llm)
        self.server_slm = ccl_lib.init_unified(keys[-2], srv_slm_bundle)

        # optimizers (trainable = LoRA + connector, the paper's AMT set)
        opt = adamw(cfg.lr, weight_decay=0.0)
        self.opt = opt
        device_opt = [opt.init(lora.partition(p)) for p in device_params]

        # registered population: push every client's personal state into
        # the host/disk-resident store; the engines then gather each
        # round's sampled working set into the stacked buffers and scatter
        # the updates back (device memory scales with the working set).
        # Only then is the frozen base kept apart from the client state:
        # store entries hold personal leaves alone.
        if self._store is not None:
            self._cohort_bases = bases
            for j in range(N):
                entry = {"train": lora.partition(device_params[j]),
                         "opt": device_opt[j]}
                if self.channel.stateful:
                    # per-client error-feedback residual rides in the store
                    # entry so it spills to disk and replays through
                    # checkpoint/resume with the rest of the personal state
                    entry["chan"] = jax.tree.map(
                        lambda a: np.zeros(np.shape(a), np.float32),
                        lora.partition(device_params[j], lora.is_lora_leaf))
                self._store.put(j, entry)
        self.server_llm_opt = opt.init(lora.partition(self.server_llm))
        self.server_slm_opt = opt.init(lora.partition(self.server_slm))

        self._se_step_raw = self._make_seccl_step()
        self._se_step = jax.jit(self._se_step_raw)

        # MMA weights (Eq. 13) depend only on the static MER masks and are
        # normalized GLOBALLY, so per-cohort partial sums recompose into
        # the flat Eq. 13 aggregate on fully-shared keys
        counts = [int(self.masks[j].sum()) for j in range(N)]
        self._mod_counts = counts
        if cfg.use_mma and cfg.mode == "mlecs":
            self._agg_weights = mma.aggregation_weights(counts)
        else:
            self._agg_weights = jnp.ones((N,)) / N

        # cohort runtimes: weights slice, shared/own key split, prox ref
        server_lora = lora.partition(self.server_slm, lora.is_lora_leaf)
        self._server_lora_dtypes = {k: v.dtype for k, v in server_lora.items()}
        self._cohorts: List[_Cohort] = []
        for c, cs in enumerate(spec.cohorts):
            rt = _Cohort(c, cs, bundles[c], spec.offsets[c])
            rt.weights = (self._agg_weights if spec.n_cohorts == 1
                          else self._agg_weights[rt.slice])
            rt.w_total = float(
                np.array(rt.weights, np.float32).sum(dtype=np.float32))
            up0 = lora.partition(device_params[rt.offset], lora.is_lora_leaf)
            rt.shared = lora.shared_keys(up0, server_lora)
            rt.own = tuple(k for k in sorted(up0) if k not in rt.shared)
            rt.own_dtypes = {k: up0[k].dtype for k in rt.own}
            # a copy, not the server SLM's own leaves: the round donates
            # the server trees, and a donated buffer may not also be read
            # through another argument of the same call
            rt.last_global = {k: jnp.copy(server_lora[k]) for k in rt.shared}
            self._cohorts.append(rt)
        if self._schedule is not None:
            woff = 0
            for rt, k in zip(self._cohorts, self._schedule.counts):
                rt.work_n, rt.work_offset = k, woff
                woff += k
        # the legacy fast path needs FULL key coverage, not just one
        # cohort: a single cohort whose server_slm has a different shape
        # (partial overlap) must still go through the shared-subset
        # machinery or the full-shape aggregate would be spliced into the
        # mismatched server tree
        self._homogeneous = (spec.n_cohorts == 1
                             and not self._cohorts[0].own
                             and len(self._cohorts[0].shared)
                             == len(server_lora))
        # the fused single-jit round additionally needs the MEAN reduction:
        # trimmed/clipped aggregation is an order statistic over raw
        # per-client uploads and runs EAGERLY (one shared op sequence
        # across engines), so robust != "mean" takes the split schedule
        self._fused = self._homogeneous and cfg.robust == "mean"

        # channel runtime per cohort: the stacked upload template (what
        # crosses the wire each round), the error-feedback residual state,
        # and the EXACT per-round byte costs (Channel.bytes_on_wire is
        # linear in the client axis, so per-client = total // work_n).
        ident = ChannelSpec().make()
        for rt in self._cohorts:
            up0 = lora.partition(device_params[rt.offset], lora.is_lora_leaf)
            rt.up_like = {
                k: jax.ShapeDtypeStruct((rt.work_n,) + v.shape, v.dtype)
                for k, v in up0.items()}
            rt.chan_state = self.channel.init_state(rt.up_like)
            rt.uplink_client_bytes = (
                self.channel.bytes_on_wire(rt.up_like) // rt.work_n)
            rt.dense_client_bytes = (
                ident.bytes_on_wire(rt.up_like) // rt.work_n)
            # the paper's Fig. 3 baseline is dense float32 uploads — the
            # actual leaves may be bf16, so track both references
            rt.f32_client_bytes = 4 * sum(
                int(np.prod(v.shape)) for v in up0.values())
            down_like = {k: server_lora[k] for k in rt.shared}
            down_like.update({k: up0[k] for k in rt.own})
            rt.downlink_bytes = self.channel.bytes_on_wire(
                {k: jax.ShapeDtypeStruct((1,) + v.shape, v.dtype)
                 for k, v in down_like.items()})
        self._bytes_up = 0
        self._bytes_up_dense = 0
        self._bytes_up_f32 = 0
        self._bytes_down = 0
        self.comm_log: List[Dict] = []

        # the stream bank: one infinite shuffle stream per GLOBAL client id
        # (plus the server's), pulled only for the clients a round actually
        # touches — a client resuming participation continues its own
        # stream.  Every engine reads the same bank, so the pre-bank
        # per-engine iterators are replayed bit-for-bit.
        self._streams = ClientStreams()
        for j in range(N):
            c = spec.cohort_of(j)
            bs_c = spec.cohort_batch_size(c)
            self._streams.register(f"pub/{j}", self.public_train, bs_c,
                                   cfg.seed + 100 + j, self.masks[j])
            self._streams.register(f"priv/{j}", self.priv_train[j], bs_c,
                                   cfg.seed + 200 + j, self.masks[j])
        self._streams.register("server", self.public_train, cfg.batch_size,
                               cfg.seed + 999)

        if self.engine in ("vectorized", "overlap"):
            for rt in self._cohorts:
                sl = rt.slice
                if self._schedule is None:
                    rt.stacked_params = lora.stack_trees(device_params[sl])
                    rt.stacked_opt = lora.stack_trees(device_opt[sl])
                else:
                    # fixed-size working-set buffers, seeded with round
                    # 0's prospective draw (so pre-run evaluation sees the
                    # state round 0 will train); each round's gather
                    # re-splices only the personal leaves — the shared
                    # frozen backbone in the buffer never moves again
                    loc0 = self._schedule.round_locals(0)[rt.idx]
                    rt.stacked_params = lora.stack_trees(
                        [device_params[rt.offset + int(i)] for i in loc0])
                    rt.stacked_opt = lora.stack_trees(
                        [device_opt[rt.offset + int(i)] for i in loc0])
                bs_c = spec.cohort_batch_size(rt.idx)
                rt.eval_blocks = max(
                    -(-self.priv_test[j]["tokens"].shape[0] // bs_c)
                    for j in range(rt.offset, rt.offset + rt.n))
                rt.client_eval_fn = seccl.make_eval_fn(
                    rt.bundle, n_clients=rt.work_n)
            # evaluation: the test sets normally never change, so the
            # padded device-stacked eval shards (and the server's
            # public-test stack) are built once and reused every round —
            # call refresh_eval_shards() after mutating priv_test /
            # public_test
            self._server_eval_fn = seccl.make_eval_fn(self.llm)
            if self.engine == "vectorized":
                if self._fused:
                    # the legacy fused single-jit round (bit-for-bit the
                    # pre-cohort engine)
                    self._round_fn = self._make_vectorized_round()
                else:
                    # multi-cohort or robust reduction: the split schedule
                    # — per-cohort device phases + an EAGER combine + the
                    # server phase.  The combine must run eagerly in every
                    # engine:
                    # inside one fused jit XLA fuses it into its consumers
                    # (server landing AND client broadcast) and the
                    # duplicated fusions round differently at bf16 ULP,
                    # which training amplifies past the engines' 1e-5
                    # agreement.
                    (self._device_phase_fns,
                     self._server_phase_fn) = self._make_overlap_phases()
                self.refresh_eval_shards()
                if self.mesh is not None:
                    self._place_on_mesh(self.mesh)
            else:
                self._init_overlap()
        else:
            for rt in self._cohorts:
                sl = rt.slice
                if self._schedule is None:
                    rt.device_params = device_params[sl]
                    rt.device_opt = device_opt[sl]
                rt.dev_ccl_step = ccl_lib.make_local_step(
                    rt.bundle, opt, ccl_weight=_ccl_weight(cfg),
                    n_negatives=cfg.n_negatives, ccl_score=cfg.ccl_score)
                rt.dev_amt_step = ccl_lib.make_local_step(
                    rt.bundle, opt, ccl_weight=0.0, with_anchor=False,
                    prox_weight=cfg.prox_weight)
                # reference evaluation: host loop over per-batch jitted
                # steps sharing the stacked engines' exact metric definition
                rt.eval_step = jax.jit(seccl.make_eval_step(rt.bundle))
            self._anchor_fn = jax.jit(
                lambda p, b: ccl_lib.server_anchors(p, self.llm, b))
            self._llm_eval_step = jax.jit(seccl.make_eval_step(self.llm))
        self.history: List[Dict] = []

    # ------------------------------------------------------------------
    @property
    def _stacked(self) -> bool:
        """True for the engines that keep client state device-stacked."""
        return self.engine in ("vectorized", "overlap")

    @property
    def cohorts(self) -> Tuple[_Cohort, ...]:
        """Read-only view of the per-cohort runtime states (offset, size,
        shared-key subset, weights) — global client ``j`` lives in the
        cohort whose ``offset <= j < offset + n``."""
        return tuple(self._cohorts)

    def _single(self) -> _Cohort:
        """The sole cohort (legacy single-cohort attribute shims)."""
        if len(self._cohorts) != 1:
            raise AttributeError(
                "this attribute is the legacy single-cohort view; use "
                "runner.cohorts[c].<attr> on multi-cohort federations")
        return self._cohorts[0]

    @property
    def store(self):
        """The registered-population :class:`~repro.core.store.ClientStore`
        (None without a sampler — all client state is then resident)."""
        return self._store

    @property
    def stacked_params(self):
        """Legacy single-cohort view of the device-stacked parameters."""
        return self._single().stacked_params

    @property
    def stacked_opt(self):
        """Legacy single-cohort view of the device-stacked opt state."""
        return self._single().stacked_opt

    @property
    def _client_eval_steps(self):
        """Legacy single-cohort view of the precomputed eval shards."""
        return self._single().eval_steps

    @property
    def device_params(self) -> List:
        """Per-device full parameter trees in GLOBAL client order
        (unstacked views under the stacked engines; materialized from the
        store — shared frozen base + personal leaves — under a sampler)."""
        if self._schedule is not None:
            return [self._loop_client_state(rt, i)[0]
                    for rt in self._cohorts for i in range(rt.n)]
        if self._stacked:
            return [p for rt in self._cohorts
                    for p in lora.unstack_tree(rt.stacked_params, rt.n)]
        return [p for rt in self._cohorts for p in rt.device_params]

    @property
    def device_opt(self) -> List:
        """Per-device optimizer states in global client order (unstacked
        views under the stacked engines; from the store under a
        sampler)."""
        if self._schedule is not None:
            return [self._loop_client_state(rt, i)[1]
                    for rt in self._cohorts for i in range(rt.n)]
        if self._stacked:
            return [o for rt in self._cohorts
                    for o in lora.unstack_tree(rt.stacked_opt, rt.n)]
        return [o for rt in self._cohorts for o in rt.device_opt]

    def _mesh_for(self, idx: int):
        """The mesh cohort ``idx`` lives on (shared, per-cohort, or None)."""
        return self._meshes[idx] if self._meshes is not None else self.mesh

    def _placement_key(self, rt: _Cohort):
        """Identity of cohort ``rt``'s client placement — cohorts with the
        same key may share downloaded server products (anchor base/
        trainables) instead of holding per-cohort copies."""
        m = self._mesh_for(rt.idx)
        return id(m) if m is not None else None

    # ------------------------------------------------------------------
    def _place_on_mesh(self, mesh):
        """Shard every cohort's client stack over the mesh "data" axis,
        replicate the server; exact no-op on a (1, 1) host mesh."""
        def clients(tree):
            return jax.device_put(tree, shard_part.stacked_client_shardings(
                tree, mesh, TRAIN_RULES, axis=0))

        def repl(tree):
            return jax.device_put(
                tree, shard_part.replicated_shardings(tree, mesh))

        for rt in self._cohorts:
            rt.stacked_params = clients(rt.stacked_params)
            rt.stacked_opt = clients(rt.stacked_opt)
            if rt.chan_state:
                # error-feedback residuals shard with the clients they
                # belong to (leading axis = client axis)
                rt.chan_state = clients(rt.chan_state)
            rt.last_global = repl(rt.last_global)
            rt.weights = repl(rt.weights)
        self.server_llm = repl(self.server_llm)
        self.server_slm = repl(self.server_slm)
        self.server_llm_opt = repl(self.server_llm_opt)
        self.server_slm_opt = repl(self.server_slm_opt)
        # eval shards are placed by refresh_eval_shards (device axis 1 of
        # the (T, N, B, ...) client stacks, server stack replicated)

    # ------------------------------------------------------------------
    # per-round fault state (no-ops without a FaultSpec)

    def _begin_round(self) -> None:
        """Advance the round counter and draw this round's host-side state:
        the sampled participant set (when a sampler is configured), the
        fault schedule's presence/straggle masks restricted to it, and the
        Eq. 13 weights renormalized over the round's *contributing* set —
        sampled AND present AND on-time, one mass rule.  Everything drawn
        here is host data the compiled rounds consume as gather indices /
        zero-weight masks, never shapes, so resampling and fault draws
        reuse the warm traces.  Called exactly once at the top of every
        engine's round; fault-free full-participation runs keep the static
        init-time weights and pay nothing."""
        with _span("fed.begin", round=self._round_idx):
            cfg = self.cfg
            rnd = self._round_idx
            self._round_idx += 1
            self._rnd_no = rnd
            ids = None
            if self._schedule is not None:
                self._rnd_locals = self._schedule.round_locals(rnd)
                self._rnd_ids = ids = np.concatenate([
                    off + loc for off, loc in zip(self.spec.offsets,
                                                  self._rnd_locals)])
                self._rnd_scale = (self._attack_scale[ids]
                                   if self._attack_scale is not None else None)
            if self._faults is None:
                if ids is None:
                    return
                # sampler without faults: weights renormalized over the
                # sampled set (the identity sampler reproduces the static
                # init-time weights bit-for-bit); presence stays None so the
                # phase functions keep their mask-free traces
                if cfg.use_mma and cfg.mode == "mlecs":
                    w = mma.sampled_weights(self._mod_counts, ids)
                else:
                    w = jnp.ones((len(ids),)) / len(ids)
                self._rnd_weights = np.array(w, np.float32)
                return
            present, ontime = self._faults.round_masks(rnd)
            if ids is not None:
                present = present[ids].copy()
                ontime = ontime[ids].copy()
                if not bool((present & ontime).any()):
                    # a sampled set whose every member failed must not push an
                    # all-zero weight vector through the server landing (it
                    # would zero the server SLM's LoRA); resurrect one member
                    # — its upload equals its pre-round params, so the
                    # aggregate is stale-but-sane
                    present[0] = ontime[0] = True
            contrib = present & ontime
            if cfg.use_mma and cfg.mode == "mlecs":
                if ids is None:
                    w = mma.aggregation_weights(self._mod_counts,
                                                present=contrib)
                else:
                    w = mma.sampled_weights(self._mod_counts, ids,
                                            present=contrib)
            else:
                w = contrib.astype(np.float32) / max(int(contrib.sum()), 1)
            self._rnd_present = present
            self._rnd_contrib = contrib
            self._rnd_weights = np.array(w, np.float32)

    def _active_weights(self) -> np.ndarray:
        """This round's globally-normalized weights as host numpy (the
        fault-masked draw when a schedule is active; static Eq. 13 else)."""
        if self._rnd_weights is not None:
            return self._rnd_weights
        return np.array(self._agg_weights, np.float32)

    def _weights_for(self, rt: _Cohort):
        """The weight slice a device phase consumes this round — traced
        DATA, so fault/sampling rounds reuse the phase's one compiled
        trace.  Per-round vectors are working-set sized; ``work_slice``
        equals the population slice without a sampler."""
        if self._rnd_weights is None:
            return rt.weights
        return jnp.asarray(self._rnd_weights[rt.work_slice])

    def _w_total_for(self, rt: _Cohort) -> float:
        """Cohort ``rt``'s weight mass this round (surviving sampled mass
        under faults — the combine's renormalization denominator)."""
        if self._rnd_weights is None:
            return rt.w_total
        return float(self._rnd_weights[rt.work_slice].sum(dtype=np.float32))

    def _present_for(self, rt: _Cohort):
        """Cohort block of the round's presence mask (None ⇒ no faults —
        the phase functions then take the mask-free trace)."""
        if self._rnd_present is None:
            return None
        return jnp.asarray(self._rnd_present[rt.work_slice])

    def _scale_for(self, rt: _Cohort):
        """Cohort block of this round's Byzantine scale vector gathered
        over the sampled set — None without a sampler (the phase closures
        then use their baked population-order constant) or without a
        scaled-update attack."""
        if self._rnd_scale is None:
            return None
        return jnp.asarray(self._rnd_scale[rt.work_slice])

    def _chan_state_for(self, rt: _Cohort):
        """Cohort ``rt``'s error-feedback residual stack — None for
        stateless codecs (the phase functions then keep their
        channel-free default traces)."""
        return rt.chan_state if self.channel.stateful else None

    def _chan_rnd(self):
        """This round's index as traced DATA for the channel (freshens
        sketch bases without retracing) — None under identity, so the
        pre-channel call signatures stay bit-identical."""
        if self.channel.is_identity:
            return None
        return jnp.asarray(self._rnd_no, jnp.int32)

    def _commit_comm(self) -> None:
        """Account one round's measured bytes-on-wire: per cohort, every
        PRESENT member's compressed upload (stragglers transmit too —
        late, weight 0 — but offline clients send nothing) plus one
        multicast downlink payload.  Standalone rounds move nothing."""
        with _span("fed.scatter", round=self._rnd_no):
            if self.cfg.mode == "standalone":
                self.comm_log.append(
                    {"round": self._rnd_no, "uplink": 0, "downlink": 0})
                return
            up = up_dense = up_f32 = down = 0
            for rt in self._cohorts:
                n = rt.work_n
                if self._rnd_present is not None:
                    n = int(np.array(
                        self._rnd_present[rt.work_slice]).sum())
                up += n * rt.uplink_client_bytes
                up_dense += n * rt.dense_client_bytes
                up_f32 += n * rt.f32_client_bytes
                down += rt.downlink_bytes
            self._bytes_up += up
            self._bytes_up_dense += up_dense
            self._bytes_up_f32 += up_f32
            self._bytes_down += down
            self.comm_log.append({"round": self._rnd_no, "uplink": int(up),
                                  "downlink": int(down)})

    @property
    def comm_stats(self) -> Dict:
        """Measured wire-traffic totals: codec, exact uplink/downlink
        bytes across all committed rounds, the dense-f32 uplink the same
        transmissions would have cost, and the resulting compression
        ratio (the benchmark's acceptance measurement — computed from
        :meth:`Channel.bytes_on_wire`, not estimated)."""
        up = int(self._bytes_up)
        dense = int(self._bytes_up_dense)
        f32 = int(self._bytes_up_f32)
        return {"codec": self.channel.spec.codec,
                "rounds": len(self.comm_log),
                "uplink_bytes": up,
                "uplink_dense_bytes": dense,
                "uplink_f32_bytes": f32,
                "uplink_ratio": (dense / up) if up else float("inf"),
                "uplink_ratio_f32": (f32 / up) if up else float("inf"),
                "downlink_bytes": int(self._bytes_down),
                "uplink_client_bytes": {
                    rt.idx: rt.uplink_client_bytes
                    for rt in self._cohorts}}

    # ------------------------------------------------------------------
    def _make_seccl_step(self):
        """Joint SE-CCL update: LLM minimizes Eq. 15, SLM minimizes Eq. 16.
        Returned unjitted — the loop engine jits it per call, the stacked
        engines scan it inside the fused round / server phase.  Uses the
        *server-side* SLM bundle (identical to the cohort bundle in the
        homogeneous case)."""
        cfg = self.cfg
        srv_slm = self._srv_slm_bundle

        def loss_pair(train_llm, train_slm, llm_params, slm_params, batch):
            llm_full = lora.combine(llm_params, train_llm)
            slm_full = lora.combine(slm_params, train_slm)
            # random anchor modality: SE-CCL anchors on one of its own
            # modality representations (omni-modal public data)
            l_llm, (_, _) = ccl_lib.mlecs_loss(
                llm_full, self.llm, batch, anchor=None,
                ccl_weight=0.5 if cfg.use_ccl else 0.0,
                n_negatives=cfg.n_negatives)
            l_slm, (_, _) = ccl_lib.mlecs_loss(
                slm_full, srv_slm, batch, anchor=None, ccl_weight=0.0)
            y_llm, _ = self.llm.logits(llm_full, batch)
            y_slm, _ = srv_slm.logits(slm_full, batch)
            kt_llm = seccl.kt_loss(y_llm, y_slm)      # LLM learns from SLM
            kt_slm = seccl.kt_loss(y_slm, y_llm)      # SLM learns from LLM
            total = (l_llm + cfg.kt_weight * kt_llm
                     + l_slm + cfg.kt_weight * kt_slm)
            return total, {"llm": l_llm, "slm": l_slm,
                           "kt_llm": kt_llm, "kt_slm": kt_slm}

        def step(llm_params, slm_params, llm_opt, slm_opt, batch):
            t_llm = lora.partition(llm_params)
            t_slm = lora.partition(slm_params)
            (loss, metrics), grads = jax.value_and_grad(
                loss_pair, argnums=(0, 1), has_aux=True)(
                    t_llm, t_slm, llm_params, slm_params, batch)
            g_llm, g_slm = grads
            u, llm_opt = self.opt.update(g_llm, llm_opt, t_llm)
            llm_params = lora.combine(llm_params, apply_updates(t_llm, u))
            u, slm_opt = self.opt.update(g_slm, slm_opt, t_slm)
            slm_params = lora.combine(slm_params, apply_updates(t_slm, u))
            return llm_params, slm_params, llm_opt, slm_opt, metrics

        return step

    # ------------------------------------------------------------------
    # the per-cohort device chain (shared by the fused vectorized round
    # and the overlap engine's device phases)

    def _make_device_steps(self, rt: _Cohort):
        """The cohort's vmapped CCL and AMT step functions (unjitted)."""
        cfg = self.cfg
        ccl_step = ccl_lib.make_stacked_step(
            rt.bundle, self.opt, ccl_weight=_ccl_weight(cfg),
            n_negatives=cfg.n_negatives, ccl_score=cfg.ccl_score)
        amt_step = ccl_lib.make_stacked_step(
            rt.bundle, self.opt, ccl_weight=0.0, with_anchor=False,
            prox_weight=cfg.prox_weight)
        return ccl_step, amt_step

    def _device_chain(self, ccl_step, amt_step, params, opt_state,
                      anchor_llm, gref, pub_steps, priv_steps):
        """(1)+(2) for one cohort: anchors + CCL scan, then the AMT scan —
        traced inside the fused round or a per-cohort device phase, under
        the scopes ``device_phase/ccl`` and ``device_phase/amt``."""
        cfg = self.cfg
        llm = self.llm

        def ccl_body(carry, batch):
            p, o = carry
            anchor = ccl_lib.stacked_server_anchors(
                anchor_llm, llm,
                dict(batch, modality_mask=jnp.ones_like(
                    batch["modality_mask"])))
            p, o, _ = ccl_step(p, o, batch, anchor)
            return (p, o), None

        def amt_body(carry, batch):
            p, o = carry
            p, o, _ = amt_step(p, o, batch, None, gref)
            return (p, o), None

        with jax.named_scope("device_phase"):
            if _do_ccl(cfg):
                with jax.named_scope("ccl"):
                    (params, opt_state), _ = jax.lax.scan(
                        ccl_body, (params, opt_state), pub_steps)
            with jax.named_scope("amt"):
                (params, opt_state), _ = jax.lax.scan(
                    amt_body, (params, opt_state), priv_steps)
        return params, opt_state

    def _seccl_scan(self, server_llm, server_slm, llm_opt, slm_opt, steps):
        """(4) SE-CCL: the joint server step scanned over ``steps``, under
        the scope ``server_phase`` — traced inside the fused round or the
        split schedule's server phase."""
        se_step = self._se_step_raw

        def se_body(carry, batch):
            s_llm, s_slm, o_llm, o_slm = carry
            s_llm, s_slm, o_llm, o_slm, _ = se_step(
                s_llm, s_slm, o_llm, o_slm, batch)
            return (s_llm, s_slm, o_llm, o_slm), None

        with jax.named_scope("server_phase"):
            carry, _ = jax.lax.scan(
                se_body, (server_llm, server_slm, llm_opt, slm_opt), steps)
        return carry

    def _cohort_delivery(self, rt: _Cohort, down: Dict, own_avg: Dict
                         ) -> Dict:
        """What cohort ``rt`` receives in Alg. 1 step 5: the server's
        values on the shared-shape subset plus the intra-cohort MMA average
        of its architecture-specific keys.  Fully-shared single cohort ⇒
        ``down`` itself — the legacy broadcast, bit-for-bit.

        Under faults a key can have aggregated nothing this round (every
        participant absent) — the combine omits it; the delivery then
        re-sends the previous global value so its tree structure (and the
        prox reference's) never changes with the fault draw."""
        if self._homogeneous:
            return down
        delivery = {}
        for k in rt.shared:
            if k in down:
                delivery[k] = down[k]
            elif k in rt.last_global:
                delivery[k] = rt.last_global[k]
        for k in rt.own:
            if k in own_avg:
                delivery[k] = own_avg[k]
            elif k in rt.last_global:
                delivery[k] = rt.last_global[k]
        return delivery

    # ------------------------------------------------------------------
    def _make_vectorized_round(self):
        """Build the single-cohort fused round function: the device phase
        (vmap over the stacked client axis, scan over local steps), MMA
        aggregation, SE-CCL, and redistribution in ONE jitted call — the
        legacy homogeneous round, bit-for-bit.  Multi-cohort federations
        use the split schedule instead (:meth:`_run_round_split`): the
        cross-cohort combine must run eagerly, outside any fusion context,
        or its duplicated fusions round differently at bf16 ULP."""
        cfg = self.cfg
        (rt,) = self._cohorts
        ccl_step, amt_step = self._make_device_steps(rt)
        seccl_scan = self._seccl_scan
        do_seccl = _do_seccl(cfg)
        with_faults = self._faults is not None
        chan = self.channel
        scale = (jnp.asarray(self._attack_scale)
                 if self._attack_scale is not None else None)

        def deliver(p, uploads, flat, present):
            """Splice the broadcast delivery into the stacked params; under
            faults, offline clients receive nothing (masked select — same
            trace, the mask is data)."""
            bcast = uploads.broadcast(flat).trainable
            if present is not None:
                cur = lora.partition(p, lora.is_lora_leaf)
                bcast = _where_clients(present, bcast, cur)
            return lora.combine(p, bcast)

        def round_fn(states, server_llm, server_slm, server_llm_opt,
                     server_slm_opt, last_globals, weights, pubs, privs,
                     server_steps, present, scales=None, chan_states=None,
                     rnd=None):
            # per-round Byzantine scale: the population-order closure
            # constant normally; under participant sampling the gathered
            # (S,) vector arrives as data (every sampled round passes it,
            # so the trace is warmed once)
            sc = scale if scales is None else scales[0]
            gref = last_globals[0] if cfg.prox_weight > 0 else None
            p, o = self._device_chain(
                ccl_step, amt_step, states[0][0], states[0][1], server_llm,
                gref, pubs[0], privs[0])
            if with_faults:
                # an offline client's round does not happen: its training
                # is undone by a per-client select (pure data flow — the
                # step count and every shape stay those of the clean trace)
                p = _where_clients(present[0], p, states[0][0])
                o = _where_clients(present[0], o, states[0][1])
            # the model devices actually serve between rounds (client eval):
            # only its LoRA leaves differ from the delivered tree, so only
            # those leave the jit (the caller splices them back in) — a
            # second full stacked copy of the backbone would not fit next
            # to the first at published widths
            post_amt = (lora.partition(p, lora.is_lora_leaf),)

            if cfg.mode == "standalone":
                return (post_amt, ((p, o),), server_llm, server_slm,
                        server_llm_opt, server_slm_opt, last_globals,
                        chan_states)

            # (3) MMA aggregation (Eq. 13) over the stacked upload axis;
            # under faults the weights arrive pre-renormalized over the
            # present-and-on-time set, so stale uploads get weight exactly 0
            uploads = lora.StackedClients(
                lora.partition(p, lora.is_lora_leaf))
            if sc is not None:
                uploads = _scale_uploads(uploads, sc)
            # the wire: what the server receives is the channel roundtrip
            # of the (possibly Byzantine-scaled) uploads.  Error-feedback
            # residuals advance only for clients that actually transmitted
            # (the same presence mask that froze their training).
            if not chan.is_identity:
                with jax.named_scope("channel"):
                    dec, new_cs = chan.roundtrip(
                        uploads.trainable,
                        chan_states[0] if chan.stateful else None, rnd)
                if chan.stateful:
                    if with_faults:
                        new_cs = _where_clients(present[0], new_cs,
                                                chan_states[0])
                    chan_states = (new_cs,)
                uploads = lora.StackedClients(dec)
            with jax.named_scope("mma"):
                agg = mma.aggregate_stacked(uploads, weights[0])

            if cfg.mode == "fedavg":
                # Multi-FedAvg: broadcast the average straight back
                # (through the downlink channel — one multicast payload)
                with jax.named_scope("channel"):
                    rx = chan.roundtrip_tree(agg, rnd)
                with jax.named_scope("redistribute"):
                    p = deliver(p, uploads, rx,
                                present[0] if with_faults else None)
                return (post_amt, ((p, o),), server_llm, server_slm,
                        server_llm_opt, server_slm_opt, (rx,), chan_states)

            server_slm = lora.combine(server_slm, agg)

            # (4) SE-CCL on the server
            if do_seccl:
                (server_llm, server_slm, server_llm_opt,
                 server_slm_opt) = seccl_scan(
                    server_llm, server_slm, server_llm_opt, server_slm_opt,
                    server_steps)

            # (5) redistribute server-SLM LoRA to every device (broadcast
            # through the downlink channel; clients see the decoded tree)
            with jax.named_scope("channel"):
                down = chan.roundtrip_tree(
                    lora.partition(server_slm, lora.is_lora_leaf), rnd)
            with jax.named_scope("redistribute"):
                p = deliver(p, uploads, down,
                            present[0] if with_faults else None)
            return (post_amt, ((p, o),), server_llm, server_slm,
                    server_llm_opt, server_slm_opt, (down,), chan_states)

        # the round consumes the client stacks and the server trees and
        # returns their successors: donating them lets the outputs reuse
        # the inputs' device memory (one copy of each model, not two)
        return jax.jit(round_fn, donate_argnums=(0, 1, 2, 3, 4))

    # ------------------------------------------------------------------
    # overlap engine: the round split into per-cohort device phases and a
    # server phase, software-pipelined across rounds

    def _init_overlap(self):
        """Engine="overlap" setup: a dedicated server device, per-cohort
        device-phase functions + the shared server phase, the staleness
        queue, and the double-buffered host prefetcher."""
        devs = jax.local_devices()
        self._client_device = devs[0]
        # the server chain runs on the last local device when more than one
        # exists, so SE-CCL training executes concurrently with the
        # cohorts' device scans.  Caveats: single-device hosts degrade to
        # the sequential schedule (still correct, no overlap), and with a
        # client mesh spanning all devices the server device also carries
        # one client shard — SE-CCL then overlaps the other shards' work
        # rather than being fully contention-free.
        self._server_device = devs[-1]
        self._server_separate = len(devs) > 1

        # client-side anchor model per cohort placement: the frozen bulk is
        # downloaded once PER DISTINCT PLACEMENT (cohorts sharing a mesh /
        # the client device share one copy — duplicating the largest
        # model's frozen bulk per cohort would multiply anchor memory by
        # n_cohorts for identical bytes); per server update only the
        # trainable (LoRA + connector) subset is re-downloaded — the
        # paper's 0.65 % communication volume is all that crosses the
        # boundary
        bases = {}
        for rt in self._cohorts:
            key = self._placement_key(rt)
            if key not in bases:
                bases[key] = self._to_client_placement(rt, self.server_llm)
            rt.anchor_base = bases[key]
            rt.anchor_tr = lora.partition(rt.anchor_base)
        put_server = lambda t: jax.device_put(t, self._server_device)
        self.server_llm = put_server(self.server_llm)
        self.server_slm = put_server(self.server_slm)
        self.server_llm_opt = put_server(self.server_llm_opt)
        self.server_slm_opt = put_server(self.server_slm_opt)
        for rt in self._cohorts:
            rt.last_global = self._to_client_placement(rt, rt.last_global)
            rt.weights = self._to_client_placement(rt, rt.weights)
            m = self._mesh_for(rt.idx)
            if m is not None:
                def clients(tree, _m=m):
                    return jax.device_put(
                        tree, shard_part.stacked_client_shardings(
                            tree, _m, TRAIN_RULES, axis=0))
                rt.stacked_params = clients(rt.stacked_params)
                rt.stacked_opt = clients(rt.stacked_opt)
            else:
                rt.stacked_params = jax.device_put(rt.stacked_params,
                                                   self._client_device)
                rt.stacked_opt = jax.device_put(rt.stacked_opt,
                                                self._client_device)
        (self._device_phase_fns,
         self._server_phase_fn) = self._make_overlap_phases()
        # server-phase outputs not yet applied to the clients; entries are
        # (down LoRA, anchor trainables, per-cohort own-key averages).
        # Popped with cfg.staleness lag.
        self._srv_q: collections.deque = collections.deque()
        self.refresh_eval_shards()
        self._start_prefetch()
        if self._schedule is not None:
            # round 0's working set is already resident (the buffers were
            # seeded from its draw); stage its gather anyway so the splice
            # path is uniform from the first round
            self._stage_gather_for(0)

    def _start_prefetch(self) -> None:
        """(Re)start the double-buffered round-assembly worker.  The
        worker must not keep a dropped runner alive: it holds only a
        weakref and exits on its own once the runner is collected
        (close() remains the deterministic path)."""
        ref = weakref.ref(self)

        def assemble():
            runner = ref()
            return None if runner is None else runner._assemble_round()

        self._prefetch = RoundPrefetcher(
            assemble, alive=lambda: ref() is not None)

    def _assemble_round(self):
        """One round's device-ready batch stacks (one pub/priv stack per
        cohort; clients live on axis 1 of the (steps, work_n, B, ...)
        leaves), pulled from the per-GLOBAL-client stream bank for exactly
        the clients the round touches — the sampled working set, or the
        whole cohort without a sampler.  The synchronous top of the stacked
        rounds — the overlap engine runs it on the prefetch worker instead
        (its own round counter runs ahead of the applied rounds, and the
        schedule's stateless replay lets the worker draw the same sampled
        sets independently), and places the server stack on its dedicated
        server device."""
        cfg = self.cfg
        spec = self.spec
        rnd = self._assemble_idx
        self._assemble_idx += 1
        with _span("fed.assemble", round=rnd):
            locals_ = (self._schedule.round_locals(rnd)
                       if self._schedule is not None else None)
            pubs, privs = [], []
            for rt in self._cohorts:
                if locals_ is None:
                    members = range(rt.offset, rt.offset + rt.n)
                else:
                    members = [rt.offset + int(i) for i in locals_[rt.idx]]
                pub = self._streams.gather_steps(
                    [f"pub/{j}" for j in members],
                    spec.cohort_steps_ccl(rt.idx)) if _do_ccl(cfg) else None
                priv = self._streams.gather_steps(
                    [f"priv/{j}" for j in members],
                    spec.cohort_steps_amt(rt.idx))
                m = self._mesh_for(rt.idx)
                if m is not None:
                    def put(tree, _m=m):
                        return jax.device_put(
                            tree, shard_part.stacked_client_shardings(
                                tree, _m, TRAIN_RULES, axis=1))
                    pub = put(pub) if pub is not None else None
                    priv = put(priv)
                pubs.append(pub)
                privs.append(priv)
            server = self._streams.stack_steps("server", cfg.server_steps) \
                if _do_seccl(cfg) else None
            if server is not None:
                srv_dev = getattr(self, "_server_device", None)
                if srv_dev is not None:
                    server = jax.device_put(server, srv_dev)
                elif self.mesh is not None:
                    server = jax.device_put(
                        server,
                        shard_part.replicated_shardings(server, self.mesh))
            return tuple(pubs), tuple(privs), server

    # ------------------------------------------------------------------
    # population layer: gather each round's sampled working set from the
    # ClientStore into the fixed-size stacked buffers, scatter it back

    def _gather_host(self, locals_):
        """Host-side store gather of one round's sampled members — one
        stacked ``{"train", "opt"}`` tree per cohort (cohorts gather
        separately: their personal key sets differ under model
        heterogeneity)."""
        return [self._store.gather([rt.offset + int(i)
                                    for i in locals_[rt.idx]])
                for rt in self._cohorts]

    def _install_working_set(self, host) -> None:
        """Splice per-cohort host-gathered ``{"train", "opt"}`` stacks into
        the resident buffers.  Only the personal (trainable + optimizer)
        leaves move; the shared frozen backbone inside ``stacked_params``
        never leaves the device — the persistent buffer is the transfer
        budget's fixed cost."""
        for rt, h in zip(self._cohorts, host):
            m = self._mesh_for(rt.idx)
            dev = getattr(self, "_client_device", None)
            train = shard_part.place_stacked(h["train"], m, TRAIN_RULES,
                                             axis=0, device=dev)
            opt = shard_part.place_stacked(h["opt"], m, TRAIN_RULES,
                                           axis=0, device=dev)
            rt.stacked_params = lora.combine(rt.stacked_params, train)
            rt.stacked_opt = opt
            if "chan" in h:
                # each sampled member brings its own error-feedback
                # residual into the working-set channel state
                rt.chan_state = shard_part.place_stacked(
                    h["chan"], m, TRAIN_RULES, axis=0, device=dev)

    def _load_working_set(self) -> None:
        """Gather this round's sampled members (drawn by
        :meth:`_begin_round`) from the store into the stacked buffers.
        The overlap engine stages round r+1's gather on a background
        thread (:meth:`_stage_next_gather`); a staged result is used only
        when it belongs to this round."""
        if self._schedule is None or not self._stacked:
            return
        with _span("fed.begin", round=self._rnd_no):
            host = None
            box = getattr(self, "_staged_gather", None)
            if box is not None:
                self._staged_gather = None
                box["thread"].join()
                if box["err"] is not None:
                    raise box["err"]
                if box["rnd"] == self._rnd_no:
                    host = box["out"]
            if host is None:
                host = self._gather_host(self._rnd_locals)
            self._install_working_set(host)

    def _scatter_working_set(self) -> None:
        """Write the trained working set back to the registered population
        (the personal subset only: the trainable partition plus the
        optimizer state — exactly what :meth:`__init__` registered)."""
        if self._schedule is None or not self._stacked:
            return
        with _span("fed.scatter", round=self._rnd_no):
            for rt in self._cohorts:
                ids = [rt.offset + int(i) for i in self._rnd_locals[rt.idx]]
                entry = {"train": lora.partition(rt.stacked_params),
                         "opt": rt.stacked_opt}
                if self.channel.stateful:
                    entry["chan"] = rt.chan_state
                self._store.scatter(ids, entry)

    def _stage_next_gather(self) -> None:
        """Overlap engine: start the NEXT round's store gather on a daemon
        thread, so disk reads / host stacking overlap the in-flight round
        the same way the data prefetcher does.  The next
        :meth:`_load_working_set` joins the thread and uses the staged
        result when the round numbers line up (they always do in steady
        state; a checkpoint restore discards the stage)."""
        if self._schedule is None:
            return
        # _begin_round already advanced the counter to the next round
        self._stage_gather_for(self._round_idx)

    def _stage_gather_for(self, rnd: int) -> None:
        """Start round ``rnd``'s store gather on a daemon thread."""
        locals_ = self._schedule.round_locals(rnd)
        box = {"out": None, "err": None, "rnd": rnd}

        def work():
            try:
                box["out"] = self._gather_host(locals_)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                box["err"] = e

        t = threading.Thread(target=work, name="store-gather", daemon=True)
        box["thread"] = t
        self._staged_gather = box
        t.start()

    def _discard_staged_gather(self) -> None:
        """Drop a pending staged gather (restore / shutdown path)."""
        box = getattr(self, "_staged_gather", None)
        if box is not None:
            self._staged_gather = None
            box["thread"].join()

    def _own_avgs(self, partials) -> Tuple[Dict, ...]:
        """Each cohort's intra-cohort MMA average of its architecture-
        specific (non-shared) keys, from its f32 partial sums — computed
        EAGERLY with one shared op sequence, so every engine rounds these
        identically (in-jit variants fuse differently at bf16 ULP).
        Under faults the divisor is the cohort's *surviving* mass; a
        cohort that lost every contributor this round averages nothing
        (its clients keep last round's own-key values)."""
        out = []
        for rt, p in zip(self._cohorts, partials):
            wt = self._w_total_for(rt)
            if not rt.own or not wt > 0.0:
                out.append({})
                continue
            out.append({k: (p[k] / np.float32(wt)).astype(rt.own_dtypes[k])
                        for k in rt.own})
        return tuple(out)

    def _decode_payloads(self, payloads):
        """Decode the cohorts' device-phase WIRE payloads back into the
        forms the identity schedule produces, eagerly, before any
        reduction.  Non-identity device phases return
        ``{"enc": codes, "state": new_residuals}`` — the server side of
        the channel pops the advanced error-feedback state, decodes the
        codes against the cohort's upload template, and only then reduces
        (robust order statistics sort per-client values, so they MUST see
        dense uploads — the decode-before-reduce rule, the same tension
        PR 7 documented for secure aggregation).  Identity payloads pass
        through untouched (the pre-channel graph, bit for bit)."""
        if self.channel.is_identity:
            return payloads
        with _span("fed.decode", round=self._rnd_no):
            cfg = self.cfg
            out = []
            for rt, pl in zip(self._cohorts, payloads):
                if self.channel.stateful:
                    rt.chan_state = pl["state"]
                dec = self.channel.decode(pl["enc"], rt.up_like)
                if cfg.robust != "mean":
                    out.append(dec)
                elif self._homogeneous:
                    out.append(mma.aggregate_stacked(
                        lora.StackedClients(dec), self._weights_for(rt)))
                else:
                    out.append(mma.partial_aggregate_stacked(
                        lora.StackedClients(dec), self._weights_for(rt)))
            return out

    def _combine_payloads(self, payloads, device=None):
        """Fold the cohorts' device-phase payloads into the server-bound
        aggregate.  Fully-shared single cohort: the payload already IS the
        legacy Eq. 13 aggregate.  Otherwise the payloads are f32 partial
        sums — take the eager own-key averages on their source placement,
        move the partials to the combine placement, and run the
        shared-subset combine, EAGERLY and in the same op sequence in
        every engine (see the split-schedule note in ``__init__``).
        Under ``robust != "mean"`` the payloads are instead RAW stacked
        uploads and the reduction routes to :meth:`_robust_combine`.
        Returns ``(agg, own_avgs)``."""
        with _span("fed.combine", round=self._rnd_no):
            if self.cfg.robust != "mean":
                return self._robust_combine(payloads, device=device)
            if self._homogeneous:
                return payloads[0], ({},)
            own_avgs = self._own_avgs(payloads)
            partials = payloads if device is None else [
                jax.device_put(p, device) for p in payloads]
            agg = mma.combine_cohort_partials(
                partials, [rt.shared for rt in self._cohorts],
                [self._w_total_for(rt) for rt in self._cohorts],
                self._server_lora_dtypes)
            return agg, own_avgs

    def _robust_combine(self, payloads, device=None):
        """The robust counterpart of :meth:`_combine_payloads`:
        ``payloads[c]`` is cohort ``c``'s RAW stacked upload dict (order
        statistics cannot be taken over pre-summed partials).  One eager
        shared op sequence — every engine hands its uploads to this exact
        reduction, so the robust paths stay structurally parity-safe the
        same way the mean combine does.  Returns ``(agg, own_avgs)``."""
        cfg = self.cfg
        w = self._active_weights()
        contrib = self._rnd_contrib          # None without a fault model
        if device is not None:
            payloads = [jax.device_put(p, device) for p in payloads]
        if self._homogeneous:
            agg = mma.aggregate_stacked(
                payloads[0], w, robust=cfg.robust, present=contrib,
                trim_frac=cfg.trim_frac)
            return agg, ({},)
        own_avgs = []
        for rt, p in zip(self._cohorts, payloads):
            wsl = w[rt.work_slice]
            csl = None if contrib is None else contrib[rt.work_slice]
            mass = float(wsl.sum() if csl is None else (wsl * csl).sum())
            if not rt.own or not mass > 0.0:
                own_avgs.append({})
                continue
            own = mma.aggregate_stacked(
                {k: p[k] for k in rt.own}, wsl, robust=cfg.robust,
                present=csl, trim_frac=cfg.trim_frac)
            own_avgs.append(own)
        agg = mma.robust_combine_cohorts(
            payloads, [w[rt.work_slice] for rt in self._cohorts],
            [rt.shared for rt in self._cohorts],
            self._server_lora_dtypes, cfg.robust,
            present=(None if contrib is None else
                     [contrib[rt.work_slice] for rt in self._cohorts]),
            trim_frac=cfg.trim_frac)
        return agg, tuple(own_avgs)

    def _stable_agg(self, agg):
        """Fill zero-mass shared keys (every participant absent this
        round) with the server's CURRENT values before the jitted server
        phase: ``lora.combine`` with the current value is the same no-op
        as omitting the key, but omitting changes the aggregate's tree
        structure with the fault draw — and a structure change retraces
        the server phase, violating the no-retrace invariant."""
        if self._rnd_present is None or self._homogeneous:
            return agg
        with _span("fed.combine", round=self._rnd_no):
            missing = [k for rt in self._cohorts for k in rt.shared
                       if k not in agg]
            if missing:
                cur = lora.partition(self.server_slm, lora.is_lora_leaf)
                agg = dict(agg)
                for k in missing:
                    # a copy: the server phase may donate the server SLM
                    agg[k] = jnp.copy(cur[k])
            return agg

    def _apply_deliveries(self, down, own_avgs) -> None:
        """Alg. 1 step 5 across cohorts: splice each cohort's delivery
        (shared subset from ``down`` + its own-key averages) into its
        stacked tree and remember it as the prox/redistribution
        reference."""
        with _span("fed.deliver", round=self._rnd_no):
            for c, rt in enumerate(self._cohorts):
                delivery = self._cohort_delivery(rt, down, own_avgs[c])
                # downlink channel: one multicast payload per cohort; clients
                # (and the prox reference) see the DECODED tree
                delivery = self.channel.roundtrip_tree(delivery, self._rnd_no)
                delivery = self._to_client_placement(rt, delivery)
                rt.stacked_params = self._redistribute(
                    rt, rt.stacked_params, delivery)
                rt.last_global = delivery

    def _make_overlap_phases(self):
        """Build the pipelined phase functions.

        * per-cohort ``device_phase`` — the cohort's CCL/AMT scans plus its
          MMA upload payload: the full aggregate in the single-cohort case
          (the legacy graph), or the f32 partial sums + the cohort-local
          key averages under heterogeneity (everything that runs at the
          edge, ending in the 0.65 %-volume upload);
        * ``server_phase`` — aggregation landing + the SE-CCL scan + the
          redistribution payload (``down`` LoRA and the anchor-model
          trainables), compiled onto the dedicated server device.
        Redistribution is NOT a jitted function: :meth:`_redistribute`
        splices the broadcast delivery into each cohort's stacked tree
        eagerly, so the frozen bulk passes through by reference — a jitted
        combine would copy every client's full frozen parameters each
        round, which at N=64 costs more than the server phase saves.

        The client stacks are donated to the device phase (each cohort's
        chain exclusively owns them, and the caller replaces them with the
        outputs), and the server optimizer states to the server phase.
        The server parameter trees are donated only by the split schedule:
        in the overlap engine a stale anchor model or an unapplied ``down``
        legitimately outlives the next phase dispatch, and on one device
        the anchor base IS the live server LLM, so donating it would
        invalidate a live reference.
        """
        cfg = self.cfg
        seccl_scan = self._seccl_scan
        do_seccl = _do_seccl(cfg)
        standalone = cfg.mode == "standalone"
        multi = not self._homogeneous
        robust = cfg.robust
        with_faults = self._faults is not None
        chan = self.channel
        donate_dev = (0, 1)                           # client stacks
        donate_srv = ((0, 1, 2, 3) if self.engine == "vectorized"
                      else (2, 3))                    # server trees / opts

        def make_device_phase(rt: _Cohort):
            ccl_step, amt_step = self._make_device_steps(rt)
            scale0 = (jnp.asarray(self._attack_scale[rt.slice])
                      if self._attack_scale is not None else None)

            def device_phase(stacked_params, stacked_opt, anchor_llm,
                             last_global, weights, pub_steps, priv_steps,
                             present, scale=None, chan_state=None, rnd=None):
                # population-order closure constant normally; the sampled
                # (work_n,) gather arrives as a traced argument under a
                # sampler (passed every round, so one warm trace)
                sc = scale0 if scale is None else scale
                gref = last_global if cfg.prox_weight > 0 else None
                new_p, new_o = self._device_chain(
                    ccl_step, amt_step, stacked_params, stacked_opt,
                    anchor_llm, gref, pub_steps, priv_steps)
                if with_faults:
                    # offline clients' rounds do not happen (masked select
                    # — the fault draw is data, the trace stays the clean
                    # round's)
                    new_p = _where_clients(present, new_p, stacked_params)
                    new_o = _where_clients(present, new_o, stacked_opt)
                stacked_params, stacked_opt = new_p, new_o
                if standalone:
                    return stacked_params, stacked_opt, ()
                uploads = lora.StackedClients(
                    lora.partition(stacked_params, lora.is_lora_leaf))
                if sc is not None:
                    uploads = _scale_uploads(uploads, sc)
                if not chan.is_identity:
                    # the device/server phase boundary IS the wire: the
                    # payload that leaves this jit holds the codec's
                    # on-wire form (int8 codes + scales / sketch factors),
                    # and the runner decodes it eagerly before any
                    # reduction (see _decode_payloads — order-statistic
                    # robust reductions need dense per-client values)
                    with jax.named_scope("channel"):
                        enc, new_state = chan.encode(
                            uploads.trainable,
                            chan_state if chan.stateful else None, rnd)
                    if chan.stateful and with_faults:
                        new_state = _where_clients(present, new_state,
                                                   chan_state)
                    return (stacked_params, stacked_opt,
                            {"enc": enc, "state": new_state})
                if robust != "mean":
                    # robust reductions are order statistics over the
                    # client axis — they need the RAW uploads at the
                    # combine point, not a pre-summed partial; the shared
                    # eager combine then reduces identically in every
                    # engine
                    return stacked_params, stacked_opt, uploads.trainable
                if not multi:
                    # legacy single-cohort: the payload IS the aggregate
                    with jax.named_scope("mma"):
                        agg = mma.aggregate_stacked(uploads, weights)
                    return stacked_params, stacked_opt, agg
                # heterogeneous: only the f32 partial leaves the jit — the
                # own-key averages and the cross-cohort combine happen
                # eagerly so every engine rounds them identically
                with jax.named_scope("mma"):
                    partial = mma.partial_aggregate_stacked(uploads, weights)
                return stacked_params, stacked_opt, partial

            return jax.jit(device_phase, donate_argnums=donate_dev)

        def server_phase(server_llm, server_slm, server_llm_opt,
                         server_slm_opt, agg, server_steps):
            server_slm = lora.combine(server_slm, agg)
            if do_seccl:
                (server_llm, server_slm, server_llm_opt,
                 server_slm_opt) = seccl_scan(
                    server_llm, server_slm, server_llm_opt, server_slm_opt,
                    server_steps)
            down = lora.partition(server_slm, lora.is_lora_leaf)
            # SE-CCL trains the LLM's LoRA *and* connector; anchors read the
            # connector, so the anchor download is the full trainable set
            anchor_tr = lora.partition(server_llm)
            return (server_llm, server_slm, server_llm_opt, server_slm_opt,
                    down, anchor_tr)

        return ([make_device_phase(rt) for rt in self._cohorts],
                jax.jit(server_phase, donate_argnums=donate_srv))

    def _redistribute(self, rt: _Cohort, stacked_params, delivery):
        """Alg. 1 step 5, eager: broadcast the cohort's delivery over its
        client axis and splice it into the stacked tree.  Frozen leaves
        pass through by reference (zero copy); only the (n, ...) LoRA
        broadcasts materialize — the same values the vectorized engine's
        in-jit broadcast produces, bit for bit.  Under faults, offline
        clients receive nothing: the broadcast is masked with THIS round's
        presence draw at apply time (under overlap staleness the delivery
        may have been produced rounds ago — what matters is who is
        reachable when it lands)."""
        bcast = {k: jnp.broadcast_to(v, (rt.work_n,) + v.shape)
                 for k, v in delivery.items()}
        if self._rnd_present is not None:
            pres = jnp.asarray(self._rnd_present[rt.work_slice])
            cur = lora.partition(stacked_params,
                                 lambda s, _b=bcast: s in _b)
            bcast = _where_clients(pres, bcast, cur)
        return lora.combine(stacked_params, bcast)

    def _to_client_placement(self, rt: _Cohort, tree):
        """Download a server-phase product (delivery LoRA, anchor
        trainables) to where cohort ``rt``'s clients live — replicated
        over the cohort's mesh, or the overlap engine's client device (the
        vectorized split schedule has no committed client device and
        leaves default placement)."""
        m = self._mesh_for(rt.idx)
        if m is not None:
            return jax.device_put(
                tree, shard_part.replicated_shardings(tree, m))
        dev = getattr(self, "_client_device", None)
        return tree if dev is None else jax.device_put(tree, dev)

    def _run_round_overlap(self, evaluate: bool = True) -> Dict:
        """One pipelined round.

        Dispatch order: every cohort's device phase *r* (consuming the
        prefetched stacks and the *staleness*-lagged anchor model) — on
        per-cohort meshes these run concurrently via async dispatch — then
        server phase *r* on the server device (consuming the combined
        shared-subset upload), then — once the queue holds more than
        ``staleness`` pending server outputs — redistribution of the
        oldest pending delivery into each cohort's stack.  With
        ``staleness=0`` the popped output is the one just pushed,
        reproducing the vectorized schedule exactly; with ``staleness=1``
        round *r*'s server phase overlaps round *r+1*'s device phases and
        its delivery lands one round late.
        """
        cfg = self.cfg
        self._begin_round()
        self._load_working_set()
        pubs, privs, server = next(self._prefetch)
        payloads, post_amts = [], []
        for c, rt in enumerate(self._cohorts):
            # stale-anchor model: frozen base + last downloaded trainables
            anchor_llm = lora.combine(rt.anchor_base, rt.anchor_tr)
            with _span("fed.dispatch", round=self._rnd_no, cohort=c):
                post_amt, rt.stacked_opt, payload = self._device_phase_fns[c](
                    rt.stacked_params, rt.stacked_opt, anchor_llm,
                    rt.last_global, self._weights_for(rt), pubs[c], privs[c],
                    self._present_for(rt), self._scale_for(rt),
                    self._chan_state_for(rt), self._chan_rnd())
            rt.stacked_params = post_amt
            post_amts.append(post_amt)
            payloads.append(payload)

        if cfg.mode == "standalone":
            self._scatter_working_set()
            self._stage_next_gather()
            self._commit_comm()
            if not evaluate:
                return {}
            return self._finalize_eval(
                self._evaluate_clients(post_amt=post_amts))

        # the 0.65 %-volume uplink: the cohorts' wire payloads decode at
        # the phase boundary, then land on the server device for the
        # shared-subset combine
        payloads = self._decode_payloads(payloads)
        agg, own_avgs = self._combine_payloads(payloads,
                                               device=self._server_device)

        if cfg.mode == "fedavg":
            # Multi-FedAvg has no server compute: the "server output" is
            # the aggregate itself (anchor model never changes)
            self._srv_q.append((agg, None, own_avgs))
        else:
            agg_srv = jax.device_put(self._stable_agg(agg),
                                     self._server_device)
            with _span("fed.server_phase", round=self._rnd_no):
                (self.server_llm, self.server_slm, self.server_llm_opt,
                 self.server_slm_opt, down, anchor_tr) = \
                    self._server_phase_fn(
                        self.server_llm, self.server_slm,
                        self.server_llm_opt, self.server_slm_opt, agg_srv,
                        server)
            self._srv_q.append((down, anchor_tr, own_avgs))

        if len(self._srv_q) > cfg.staleness:
            down, anchor_tr, oa = self._srv_q.popleft()
            self._apply_deliveries(down, oa)
            if anchor_tr is not None:
                # one download per distinct client placement, shared by
                # the cohorts living there
                puts = {}
                for rt in self._cohorts:
                    key = self._placement_key(rt)
                    if key not in puts:
                        puts[key] = self._to_client_placement(rt, anchor_tr)
                    rt.anchor_tr = puts[key]

        # the sampled members' final state (post-AMT + any landed
        # delivery) returns to the population; round r+1's gather starts
        # in the background while this round's eval / next dispatch runs
        self._scatter_working_set()
        self._stage_next_gather()
        self._commit_comm()

        if not evaluate:
            return {}
        # client metrics on the post-AMT models, exactly like the other
        # engines (the model a device serves between rounds)
        return self._finalize_eval(
            self._evaluate_clients(post_amt=post_amts))

    # ------------------------------------------------------------------
    def run_round(self, evaluate: bool = True) -> Dict:
        """One communication round.

        With ``evaluate=True`` (default) returns the full metrics dict
        (``client`` per-device list in global client order, ``server``,
        ``summary``): client-side metrics are measured on the *post-AMT*
        device models (the model a device actually serves between rounds,
        before redistribution); server metrics after SE-CCL.
        Redistribution (Alg. 1 step 5) seeds the NEXT round's devices.

        ``evaluate=False`` skips ALL metric computation and returns ``{}``
        — the round's training state still advances identically, but no
        eval forward passes run and nothing syncs to the host, so
        benchmarks can time the engines themselves (pair with
        :meth:`sync`).  Call :meth:`evaluate_clients` /
        :meth:`evaluate_server` / :meth:`evaluate` afterwards to measure
        the eval phases separately.
        """
        with _span("fed.round", round=self._round_idx):
            if self.engine == "vectorized":
                return self._run_round_vectorized(evaluate)
            if self.engine == "overlap":
                return self._run_round_overlap(evaluate)
            return self._run_round_loop(evaluate)

    # ------------------------------------------------------------------
    def _run_round_vectorized(self, evaluate: bool = True) -> Dict:
        if not self._fused:
            return self._run_round_split(evaluate)
        cfg = self.cfg
        self._begin_round()
        self._load_working_set()
        pubs, privs, server = self._assemble_round()
        states = tuple((rt.stacked_params, rt.stacked_opt)
                       for rt in self._cohorts)
        lgs = tuple(rt.last_global for rt in self._cohorts)
        ws = tuple(self._weights_for(rt) for rt in self._cohorts)
        pres = tuple(self._present_for(rt) for rt in self._cohorts)
        scs = (tuple(self._scale_for(rt) for rt in self._cohorts)
               if self._rnd_scale is not None else None)
        css = (tuple(rt.chan_state for rt in self._cohorts)
               if self.channel.stateful else None)
        with _span("fed.dispatch", round=self._rnd_no):
            (post_amt, states, self.server_llm, self.server_slm,
             self.server_llm_opt, self.server_slm_opt, lgs,
             css) = self._round_fn(
                states, self.server_llm, self.server_slm,
                self.server_llm_opt, self.server_slm_opt, lgs, ws, pubs,
                privs, server, pres, scs, css, self._chan_rnd())
        for rt, (p, o), lg in zip(self._cohorts, states, lgs):
            rt.stacked_params, rt.stacked_opt, rt.last_global = p, o, lg
        post_amt = tuple(lora.combine(rt.stacked_params, pa)
                         for rt, pa in zip(self._cohorts, post_amt))
        if self.channel.stateful:
            for rt, cs in zip(self._cohorts, css):
                rt.chan_state = cs
        self._scatter_working_set()
        self._commit_comm()

        if not evaluate:
            return {}
        # all clients' evals in one jitted scan-over-vmap call per cohort
        return self._finalize_eval(self._evaluate_clients(post_amt=post_amt))

    def _run_round_split(self, evaluate: bool = True) -> Dict:
        """The multi-cohort vectorized round: the overlap engine's phase
        functions dispatched *synchronously* — per-cohort device phases,
        the eager cross-cohort combine, the server phase, and immediate
        redistribution.  No pipelining, no staleness, no prefetch thread;
        anchors always come from the live server LLM."""
        cfg = self.cfg
        self._begin_round()
        self._load_working_set()
        pubs, privs, server = self._assemble_round()
        payloads, post_amts = [], []
        for c, rt in enumerate(self._cohorts):
            with _span("fed.dispatch", round=self._rnd_no, cohort=c):
                post_amt, rt.stacked_opt, payload = self._device_phase_fns[c](
                    rt.stacked_params, rt.stacked_opt, self.server_llm,
                    rt.last_global, self._weights_for(rt), pubs[c], privs[c],
                    self._present_for(rt), self._scale_for(rt),
                    self._chan_state_for(rt), self._chan_rnd())
            rt.stacked_params = post_amt
            post_amts.append(post_amt)
            payloads.append(payload)

        if cfg.mode != "standalone":
            payloads = self._decode_payloads(payloads)
            agg, own_avgs = self._combine_payloads(payloads)
            if cfg.mode == "fedavg":
                self._apply_deliveries(agg, own_avgs)
            else:
                agg = self._stable_agg(agg)
                with _span("fed.server_phase", round=self._rnd_no):
                    (self.server_llm, self.server_slm, self.server_llm_opt,
                     self.server_slm_opt, down, _) = self._server_phase_fn(
                        self.server_llm, self.server_slm,
                        self.server_llm_opt, self.server_slm_opt, agg,
                        server)
                self._apply_deliveries(down, own_avgs)
        self._scatter_working_set()
        self._commit_comm()

        if not evaluate:
            return {}
        return self._finalize_eval(
            self._evaluate_clients(post_amt=post_amts))

    # ------------------------------------------------------------------
    def _pull_jnp(self, name: str) -> Dict:
        """One host batch from the stream bank as device arrays (the loop
        engine's per-step granularity)."""
        return {k: jnp.asarray(v)
                for k, v in self._streams.pull(name).items()}

    def _loop_client_state(self, rt: _Cohort, i: int):
        """Client ``rt.offset + i``'s full params + opt under the loop
        engine: the resident per-client lists normally, or materialized
        from the store (shared frozen base + the client's personal leaves)
        under a sampler."""
        if self._schedule is None:
            return rt.device_params[i], rt.device_opt[i]
        st = self._store.get(rt.offset + i)
        p = lora.combine(self._cohort_bases[rt.idx],
                         {k: jnp.asarray(v) for k, v in st["train"].items()})
        return p, jax.tree.map(jnp.asarray, st["opt"])

    def _run_round_loop(self, evaluate: bool = True) -> Dict:
        cfg = self.cfg
        spec = self.spec
        self._begin_round()
        pres = self._rnd_present     # working-set order under a sampler
        scale = self._attack_scale   # population order always
        sampled = self._schedule is not None
        # (2) device side: CCL then AMT, cohort by cohort.  Only the
        # round's members train; under a sampler each member's state is
        # materialized from the store and written back post-AMT (so
        # mid-round eval reads the post-AMT model, like the other engines)
        uploads: List[List[Dict]] = []
        for rt in self._cohorts:
            k_ccl = spec.cohort_steps_ccl(rt.idx)
            k_amt = spec.cohort_steps_amt(rt.idx)
            members = ([int(i) for i in self._rnd_locals[rt.idx]]
                       if sampled else list(range(rt.n)))
            ups = []
            for pos, i in enumerate(members):
                j = rt.offset + i
                row = rt.work_slice.start + pos if sampled else j
                p, o = self._loop_client_state(rt, i)
                if pres is not None and not pres[row]:
                    # offline: the round does not happen for this device —
                    # but its shuffle streams must still advance, or the
                    # stacked engines' replay of the per-GLOBAL-client
                    # streams would desynchronize from this reference
                    if _do_ccl(cfg):
                        self._streams.advance(f"pub/{j}", k_ccl)
                    self._streams.advance(f"priv/{j}", k_amt)
                    ups.append(lora.partition(p, lora.is_lora_leaf))
                    continue
                if _do_ccl(cfg):
                    for _ in range(k_ccl):
                        pub = self._pull_jnp(f"pub/{j}")
                        anchor = self._anchor_fn(self.server_llm, dict(
                            pub,
                            modality_mask=jnp.ones_like(pub["modality_mask"]),
                            modality_feats=pub["modality_feats"]))
                        p, o, _ = rt.dev_ccl_step(p, o, pub, anchor)
                gref = rt.last_global if cfg.prox_weight > 0 else None
                for _ in range(k_amt):
                    p, o, _ = rt.dev_amt_step(p, o,
                                              self._pull_jnp(f"priv/{j}"),
                                              None, gref)
                if sampled:
                    entry = {"train": lora.partition(p), "opt": o}
                    if self.channel.stateful:
                        # the put overwrites the WHOLE entry — carry the
                        # error-feedback residual forward (it advances in
                        # _loop_encode_uploads after all members train)
                        entry["chan"] = self._store.get(j)["chan"]
                    self._store.put(j, entry)
                else:
                    rt.device_params[i], rt.device_opt[i] = p, o
                ups.append(lora.partition(p, lora.is_lora_leaf))
            if scale is not None:
                # Byzantine scaled-update: ALL marked clients report
                # scale×u (presence doesn't matter — a stale upload has
                # weight 0 anyway, and the stacked engines scale the whole
                # vector unconditionally)
                ups = [attacks.scaled_update(u, float(scale[rt.offset + i]))
                       if scale[rt.offset + i] != 1.0 else u
                       for i, u in zip(members, ups)]
            uploads.append(ups)

        client_eval = self._evaluate_clients() if evaluate else None

        if cfg.mode == "standalone":
            self._commit_comm()
            return self._finalize_eval(client_eval) if evaluate else {}

        # the uplink wire: every member's (possibly Byzantine-scaled)
        # report crosses the channel before any reduction sees it
        if not self.channel.is_identity:
            uploads = self._loop_encode_uploads(uploads)

        # (3) MMA aggregation (Eq. 13) with the weights computed at init
        # (MER masks are static) — shared with the stacked engines, so the
        # uniform-vs-MMA gating cannot diverge.  The scan-ordered reduction
        # matters: a plain eager sum rounds differently (FMA contraction)
        # at bf16 ULP scale, which training then amplifies past the
        # engines' 1e-5 agreement.  Cross-cohort, the same
        # partials-then-combine sequence as the fused round runs eagerly.
        # Robust reductions hand the RAW stacked uploads to the shared
        # eager combine — identical op sequence to the stacked engines.
        if cfg.robust != "mean":
            agg, own_avgs = self._combine_payloads(
                [lora.StackedClients.stack(ups).trainable
                 for ups in uploads])
        elif self._homogeneous:
            agg = mma.aggregate_stacked(
                lora.StackedClients.stack(uploads[0]),
                self._weights_for(self._cohorts[0]))
            own_avgs: Tuple[Dict, ...] = ({},)
        else:
            agg, own_avgs = self._combine_payloads([
                mma.partial_aggregate_stacked(
                    lora.StackedClients.stack(ups), self._weights_for(rt))
                for rt, ups in zip(self._cohorts, uploads)])

        if cfg.mode == "fedavg":
            # Multi-FedAvg: broadcast the average straight back (offline
            # clients receive nothing; the broadcast crosses the downlink
            # channel once per cohort)
            for c, rt in enumerate(self._cohorts):
                delivery = self.channel.roundtrip_tree(
                    self._cohort_delivery(rt, agg, own_avgs[c]),
                    self._rnd_no)
                rt.last_global = delivery
                self._loop_deliver(rt, delivery, pres)
            self._commit_comm()
            return self._finalize_eval(client_eval) if evaluate else {}

        self.server_slm = lora.combine(self.server_slm, agg)

        # (4) SE-CCL on the server — gated on the SHARED predicate (the
        # engine-parity bugfix: a bare ``cfg.use_seccl`` here diverges from
        # the stacked engines for any future non-mlecs mode that reaches
        # this point)
        if _do_seccl(cfg):
            for _ in range(cfg.server_steps):
                batch = self._pull_jnp("server")
                (self.server_llm, self.server_slm, self.server_llm_opt,
                 self.server_slm_opt, _) = self._se_step(
                    self.server_llm, self.server_slm,
                    self.server_llm_opt, self.server_slm_opt, batch)

        # (5) redistribute the server-SLM LoRA: shared subset from the
        # server, cohort-local keys from the intra-cohort average (offline
        # clients receive nothing)
        down = lora.partition(self.server_slm, lora.is_lora_leaf)
        for c, rt in enumerate(self._cohorts):
            delivery = self.channel.roundtrip_tree(
                self._cohort_delivery(rt, down, own_avgs[c]), self._rnd_no)
            rt.last_global = delivery
            self._loop_deliver(rt, delivery, pres)
        self._commit_comm()
        return self._finalize_eval(client_eval) if evaluate else {}

    def _loop_deliver(self, rt: _Cohort, delivery: Dict, pres) -> None:
        """Alg. 1 step 5 for the loop engine: splice the delivery into
        each reachable member's params — the resident per-client trees, or
        the stored personal leaves under a sampler (a delivery key outside
        a client's personal set — none today — would be dropped rather
        than grow its stored tree)."""
        if self._schedule is None:
            for i in range(rt.n):
                if pres is None or pres[rt.offset + i]:
                    rt.device_params[i] = lora.combine(
                        rt.device_params[i], delivery)
            return
        for pos, i in enumerate(self._rnd_locals[rt.idx]):
            row = rt.work_slice.start + pos
            if pres is not None and not pres[row]:
                continue
            j = rt.offset + int(i)
            st = self._store.get(j)
            tr = dict(st["train"])
            for k, v in delivery.items():
                if k in tr:
                    tr[k] = np.array(v)
            # dict(st, ...) keeps every other entry key — notably the
            # channel's "chan" error-feedback residual — intact
            self._store.put(j, dict(st, train=tr))

    def _loop_encode_uploads(self, uploads: List[List[Dict]]
                             ) -> List[List[Dict]]:
        """Roundtrip the loop engine's per-client uploads through the
        channel, stacked per cohort — quantized tiles never cross the
        client axis, so the stacked encode equals each client encoding
        alone while reproducing the stacked engines' exact op sequence.
        Error-feedback residuals live in ``rt.chan_state`` (resident) or
        each member's store entry under a sampler; they advance only for
        PRESENT clients and return to where they came from."""
        chan = self.channel
        sampled = self._schedule is not None
        out = []
        for rt, ups in zip(self._cohorts, uploads):
            stacked = lora.StackedClients.stack(ups).trainable
            st = ids = None
            if chan.stateful:
                if sampled:
                    ids = [rt.offset + int(i)
                           for i in self._rnd_locals[rt.idx]]
                    st = {k: jnp.asarray(v) for k, v in
                          self._store.gather(ids)["chan"].items()}
                else:
                    st = rt.chan_state
            dec, new_state = chan.roundtrip(stacked, st, self._rnd_no)
            if chan.stateful:
                pres_c = self._present_for(rt)
                if pres_c is not None:
                    new_state = _where_clients(pres_c, new_state, st)
                if sampled:
                    for pos, cid in enumerate(ids):
                        entry = dict(self._store.get(cid))
                        entry["chan"] = jax.tree.map(
                            lambda a, _p=pos: np.array(a[_p]), new_state)
                        self._store.put(cid, entry)
                else:
                    rt.chan_state = new_state
            out.append([{k: v[i] for k, v in dec.items()}
                        for i in range(len(ups))])
        return out

    # ------------------------------------------------------------------
    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-trace counts of the engine's round functions — the
        no-retrace invariant's measurement hook.  Fault draws are DATA
        (zero-weight masks), never shapes: after the warm-up round every
        subsequent round (dropout, stragglers, Byzantine scaling included)
        must leave these counts unchanged."""
        out: Dict[str, int] = {}
        if self.engine == "loop":
            for rt in self._cohorts:
                out[f"ccl_step/{rt.idx}"] = rt.dev_ccl_step._cache_size()
                out[f"amt_step/{rt.idx}"] = rt.dev_amt_step._cache_size()
            out["se_step"] = self._se_step._cache_size()
            out["anchor_fn"] = self._anchor_fn._cache_size()
            return out
        if self.engine == "vectorized" and self._fused:
            out["round_fn"] = self._round_fn._cache_size()
            return out
        for c, fn in enumerate(self._device_phase_fns):
            out[f"device_phase/{c}"] = fn._cache_size()
        out["server_phase"] = self._server_phase_fn._cache_size()
        return out

    # ------------------------------------------------------------------
    def sync(self) -> "FederatedRunner":
        """Block until the round's *critical-path* computation has
        materialized (jax dispatch is async; benchmark timing must not
        measure enqueue).  Under the overlap engine the critical path is
        the device side only — the server chain is deliberately pipelined
        off it; use :meth:`drain` to block on everything."""
        state = tuple(self._resident_client_state(rt)
                      for rt in self._cohorts)
        if self.engine == "overlap":
            jax.block_until_ready(state)
            return self
        jax.block_until_ready((state, self.server_llm, self.server_slm))
        return self

    def _resident_client_state(self, rt: _Cohort):
        """The cohort's device-resident client state (the sync barrier's
        operand): the stacked buffers, the per-client lists, or nothing —
        the loop engine under a sampler keeps client state host-side in
        the store."""
        if self._stacked:
            return (rt.stacked_params, rt.stacked_opt)
        if self._schedule is not None:
            return ()
        return tuple(rt.device_params)

    # ------------------------------------------------------------------
    def drain(self) -> "FederatedRunner":
        """Block until ALL in-flight work has materialized — every
        cohort's device state, the server chain, and any pipelined server
        outputs not yet applied to the clients.  The overlap engine's
        full-state barrier (a superset of :meth:`sync`); cheap and
        equivalent to :meth:`sync` for the other engines."""
        state = tuple((self._resident_client_state(rt), rt.last_global)
                      for rt in self._cohorts)
        pending = list(getattr(self, "_srv_q", ()))
        jax.block_until_ready((state, self.server_llm, self.server_slm,
                               pending))
        return self

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Stop the overlap engine's prefetch worker and any background
        eval-shard rebuild (no-op for the other engines).  Safe to call
        more than once."""
        self._join_eval_refresh()
        self._discard_staged_gather()
        pf = getattr(self, "_prefetch", None)
        if pf is not None:
            self._prefetch = None
            pf.close()

    # ------------------------------------------------------------------
    def run(self) -> List[Dict]:
        """Run ``cfg.rounds`` evaluated rounds, appending to ``history``."""
        for _ in range(self.cfg.rounds):
            self.history.append(self.run_round())
        return self.history

    # ------------------------------------------------------------------
    # checkpoint / resume — the whole run state as ONE pytree through
    # CheckpointManager.  Restore resets the round counter and replays
    # the stream bank by per-round pull counts (no rng state crosses the
    # boundary), so rounds r+1..r+k after a restore re-draw the same
    # sampled sets / fault masks and consume the same data as the
    # uninterrupted run — bit-identically.

    def checkpoint_state(self) -> Dict:
        """The run state pytree: the round counter, server models +
        optimizers, per-cohort deliveries, and every client's personal
        state (the store under a sampler; the stacked trainable/opt
        buffers or per-client lists otherwise).  Refuses mid-pipeline
        overlap state — a non-empty staleness queue is not a round
        boundary (drain by finishing the round first; ``staleness=0``
        empties it every round)."""
        if len(getattr(self, "_srv_q", ())) > 0:
            raise RuntimeError(
                "cannot checkpoint with pending pipelined server outputs "
                "(overlap staleness queue is non-empty)")
        if self._schedule is not None:
            clients = self._store.state_pytree()
        elif self._stacked:
            clients = tuple(
                (lora.partition(rt.stacked_params), rt.stacked_opt)
                for rt in self._cohorts)
        else:
            clients = tuple(
                (tuple(lora.partition(p) for p in rt.device_params),
                 tuple(rt.device_opt))
                for rt in self._cohorts)
        state = {
            "round": np.int64(self._round_idx),
            "server_llm": self.server_llm,
            "server_slm": self.server_slm,
            "server_llm_opt": self.server_llm_opt,
            "server_slm_opt": self.server_slm_opt,
            "last_global": tuple(rt.last_global for rt in self._cohorts),
            "clients": clients,
        }
        if self.channel.stateful and self._schedule is None:
            # error-feedback residuals (under a sampler they already ride
            # in the store entries above; identity/sketch runs add no key
            # — the checkpoint format is unchanged for them)
            state["channel"] = tuple(rt.chan_state for rt in self._cohorts)
        return state

    def save_checkpoint(self, mgr, step: Optional[int] = None) -> int:
        """Write the run state at the current round boundary; returns the
        step used (defaults to the completed-round count)."""
        step = self._round_idx if step is None else int(step)
        mgr.save(step, self.checkpoint_state())
        return step

    def load_checkpoint(self, mgr, step: Optional[int] = None
                        ) -> "FederatedRunner":
        """Restore a run state saved by :meth:`save_checkpoint` into this
        (identically-constructed) runner and fast-forward the data streams
        to the restored round."""
        state = mgr.restore(self.checkpoint_state(), step)
        self._restore_state(state)
        return self

    def _restore_state(self, state: Dict) -> None:
        # the overlap engine's background workers consume the stream bank
        # and the store — stop them before touching either
        was_overlap = self.engine == "overlap"
        if was_overlap:
            self._join_eval_refresh()
            self._discard_staged_gather()
            pf = getattr(self, "_prefetch", None)
            if pf is not None:
                self._prefetch = None
                pf.close()
            self._srv_q.clear()
        rnd = int(np.array(state["round"]))
        self._round_idx = rnd
        self._assemble_idx = rnd
        self._rnd_present = self._rnd_contrib = self._rnd_weights = None
        self._rnd_locals = self._rnd_ids = self._rnd_no = None
        self._rnd_scale = None

        # server state back to its engine placement
        if was_overlap:
            def put(t):
                return jax.device_put(t, self._server_device)
        elif self._stacked and self.mesh is not None:
            def put(t):
                return jax.device_put(
                    t, shard_part.replicated_shardings(t, self.mesh))
        else:
            def put(t):
                return t
        self.server_llm = put(state["server_llm"])
        self.server_slm = put(state["server_slm"])
        self.server_llm_opt = put(state["server_llm_opt"])
        self.server_slm_opt = put(state["server_slm_opt"])
        for rt, lg in zip(self._cohorts, state["last_global"]):
            rt.last_global = self._to_client_placement(rt, lg)
        if was_overlap:
            # staleness queue empty at a checkpoint boundary ⇒ the live
            # anchor trainables equal the server LLM's current trainables
            anchor = lora.partition(self.server_llm)
            puts = {}
            for rt in self._cohorts:
                key = self._placement_key(rt)
                if key not in puts:
                    puts[key] = self._to_client_placement(rt, anchor)
                rt.anchor_tr = puts[key]

        # client state
        if self._schedule is not None:
            self._store.load_state_pytree(state["clients"])
            if self._stacked:
                # reload the working set the next round will draw
                self._install_working_set(self._gather_host(
                    self._schedule.round_locals(rnd)))
        elif self._stacked:
            for rt, (train, opt) in zip(self._cohorts, state["clients"]):
                m = self._mesh_for(rt.idx)
                dev = getattr(self, "_client_device", None)
                train = shard_part.place_stacked(
                    train, m, TRAIN_RULES, axis=0, device=dev)
                rt.stacked_params = lora.combine(rt.stacked_params, train)
                rt.stacked_opt = shard_part.place_stacked(
                    opt, m, TRAIN_RULES, axis=0, device=dev)
        else:
            for rt, (trains, opts) in zip(self._cohorts, state["clients"]):
                for i, (tr, o) in enumerate(zip(trains, opts)):
                    rt.device_params[i] = lora.combine(
                        rt.device_params[i], tr)
                    rt.device_opt[i] = o
        if "channel" in state:
            for rt, cs in zip(self._cohorts, state["channel"]):
                if self._stacked:
                    rt.chan_state = shard_part.place_stacked(
                        cs, self._mesh_for(rt.idx), TRAIN_RULES, axis=0,
                        device=getattr(self, "_client_device", None))
                else:
                    rt.chan_state = jax.tree.map(jnp.asarray, cs)

        # data streams: re-create at position 0 and replay the completed
        # rounds' pull counts
        self._streams.reset()
        self._replay_streams(rnd)
        if was_overlap:
            self._start_prefetch()
            if self._schedule is not None:
                self._stage_gather_for(rnd)

    def _replay_streams(self, rounds: int) -> None:
        """Fast-forward the stream bank past ``rounds`` completed rounds.
        Every engine consumes identical per-round pull counts (absent
        clients under faults advance their streams too; only sampled
        members pull at all), so the replay is engine-independent."""
        cfg = self.cfg
        spec = self.spec
        pulls: Dict[str, int] = {}
        for r in range(rounds):
            locals_ = (self._schedule.round_locals(r)
                       if self._schedule is not None else None)
            for rt in self._cohorts:
                members = (range(rt.n) if locals_ is None
                           else [int(i) for i in locals_[rt.idx]])
                k_ccl = spec.cohort_steps_ccl(rt.idx)
                k_amt = spec.cohort_steps_amt(rt.idx)
                for i in members:
                    j = rt.offset + i
                    if _do_ccl(cfg):
                        pulls[f"pub/{j}"] = pulls.get(f"pub/{j}", 0) + k_ccl
                    pulls[f"priv/{j}"] = pulls.get(f"priv/{j}", 0) + k_amt
            if _do_seccl(cfg):
                pulls["server"] = pulls.get("server", 0) + cfg.server_steps
        for name, k in pulls.items():
            self._streams.advance(name, k)

    # ------------------------------------------------------------------
    # evaluation — one metric definition (seccl.make_eval_step) under all
    # engines; see the module docstring for the engine contract

    def _active_locals(self) -> List[np.ndarray]:
        """The per-cohort sampled local indices the CURRENT client state
        belongs to: this round's draw once :meth:`_begin_round` ran, or
        the upcoming round's prospective draw between runs (the stacked
        buffers were seeded / scattered from exactly that state)."""
        if self._rnd_locals is not None:
            return self._rnd_locals
        return self._schedule.round_locals(self._round_idx)

    def _active_ids(self) -> np.ndarray:
        """The sampled GLOBAL client ids of :meth:`_active_locals`."""
        return np.concatenate([
            off + loc for off, loc in zip(self.spec.offsets,
                                          self._active_locals())])

    def _sampled_eval_steps(self, rt: _Cohort, members):
        """Padded device-stacked eval shards for one cohort's sampled
        members, cached by member tuple (FIFO-capped — repeated draws of
        small populations reuse their shards).  The block count is forced
        to the cohort's fixed ``eval_blocks``, so eval shapes never depend
        on the draw and the jitted eval scan keeps one trace."""
        key = tuple(int(i) for i in members)
        steps = rt.eval_cache.get(key)
        if steps is not None:
            return steps
        js = [rt.offset + i for i in key]
        steps = stack_eval_steps(stacked_eval_batches(
            [self.priv_test[j] for j in js],
            self.spec.cohort_batch_size(rt.idx),
            self.masks[np.array(js)], n_blocks=rt.eval_blocks))
        m = self._mesh_for(rt.idx)
        if m is not None:
            steps = jax.device_put(steps, shard_part.stacked_eval_shardings(
                steps, m, TRAIN_RULES))
        if len(rt.eval_cache) >= 8:
            rt.eval_cache.pop(next(iter(rt.eval_cache)))
        rt.eval_cache[key] = steps
        return steps

    def _evaluate_clients(self, post_amt=None) -> List[Dict]:
        """Per-device test metrics on the current (or the given per-cohort
        post-AMT stacked) device models — the full population in global
        client order, or the round's sampled participants (still in global
        id order: draws are sorted) under a sampler.
        Stacked: one jitted scan-over-vmap per cohort over its padded eval
        shards; loop: reference host loop, one device at a time."""
        self._join_eval_refresh()
        sampled = self._schedule is not None
        if self._stacked:
            out = []
            for c, rt in enumerate(self._cohorts):
                sp = post_amt[c] if post_amt is not None \
                    else rt.stacked_params
                steps = (self._sampled_eval_steps(
                             rt, self._active_locals()[rt.idx])
                         if sampled else rt.eval_steps)
                sums = rt.client_eval_fn(sp, steps)
                host = {k: np.array(v) for k, v in sums.items()}
                out.extend(
                    seccl.metrics_from_sums({k: host[k][i] for k in host})
                    for i in range(rt.work_n))
            return out
        if sampled:
            return [self._eval_model(
                        rt.eval_step,
                        self._loop_client_state(rt, int(i))[0],
                        self.priv_test[rt.offset + int(i)],
                        self.masks[rt.offset + int(i)],
                        self.spec.cohort_batch_size(rt.idx))
                    for rt in self._cohorts
                    for i in self._active_locals()[rt.idx]]
        return [self._eval_model(rt.eval_step, rt.device_params[i],
                                 self.priv_test[rt.offset + i],
                                 self.masks[rt.offset + i],
                                 self.spec.cohort_batch_size(rt.idx))
                for rt in self._cohorts for i in range(rt.n)]

    def _eval_server(self) -> Dict:
        """Server (cloud LLM) metrics on the public test set — the SE-CCL
        evaluation.  N-independent; the stacked engines run it as one
        jitted scan so it cannot dominate small-N rounds."""
        self._join_eval_refresh()
        if self._stacked:
            return seccl.metrics_from_sums(self._server_eval_fn(
                self.server_llm, self._server_eval_steps))
        return self._eval_model(self._llm_eval_step, self.server_llm,
                                self.public_test, None)

    def refresh_eval_shards(self) -> None:
        """(Re)build the stacked engines' precomputed eval stacks from the
        CURRENT ``priv_test`` / ``public_test`` (per cohort).  The shards
        are snapshotted for reuse across rounds, so after mutating a test
        set call this — otherwise the stacked engines would keep evaluating
        the stale snapshot while the loop engine (which reads the
        attributes live) sees the new data.  No-op on the loop engine.

        Under the overlap engine the rebuild runs on a background thread
        (batching + device_put are pure host work — they overlap the
        in-flight round like the data prefetcher does) and is joined
        before the next evaluation reads the stacks; results are
        identical to the synchronous rebuild."""
        if not self._stacked:
            return
        if (self.engine == "overlap"
                and getattr(self, "_prefetch", None) is not None):
            self._join_eval_refresh()
            box = {"err": None}

            def work():
                try:
                    self._build_eval_shards()
                except BaseException as e:      # noqa: BLE001 — re-raised
                    box["err"] = e              # at the join point

            t = threading.Thread(target=work, name="eval-shard-refresh",
                                 daemon=True)
            box["thread"] = t
            self._eval_refresh = box
            t.start()
            return
        self._build_eval_shards()

    def _join_eval_refresh(self) -> None:
        """Wait for a pending background eval-shard rebuild (if any) and
        surface its error on the caller's thread."""
        box = getattr(self, "_eval_refresh", None)
        if box is None:
            return
        self._eval_refresh = None
        box["thread"].join()
        if box["err"] is not None:
            raise box["err"]

    def _build_eval_shards(self) -> None:
        bs = self.cfg.batch_size
        if self._schedule is None:
            for rt in self._cohorts:
                sl = rt.slice
                rt.eval_steps = stack_eval_steps(stacked_eval_batches(
                    self.priv_test[sl],
                    self.spec.cohort_batch_size(rt.idx), self.masks[sl]))
                m = self._mesh_for(rt.idx)
                if m is not None:
                    rt.eval_steps = jax.device_put(
                        rt.eval_steps, shard_part.stacked_eval_shardings(
                            rt.eval_steps, m, TRAIN_RULES))
        else:
            # sampled working sets build their shards lazily per draw
            # (:meth:`_sampled_eval_steps`); a refresh invalidates the
            # cache so mutated test data is picked up
            for rt in self._cohorts:
                rt.eval_cache.clear()
        self._server_eval_steps = stack_eval_steps(
            np_eval_batches(self.public_test, bs))
        if self.engine == "overlap":
            # the server evaluates itself where its chain lives
            self._server_eval_steps = jax.device_put(
                self._server_eval_steps, self._server_device)
        elif self.mesh is not None:
            self._server_eval_steps = jax.device_put(
                self._server_eval_steps, shard_part.replicated_shardings(
                    self._server_eval_steps, self.mesh))

    def evaluate_clients(self) -> List[Dict]:
        """Public API: per-device ``{"ce", "acc"}`` on each private test
        set (global client order), using the engine's native eval path."""
        return self._evaluate_clients()

    def evaluate_server(self) -> Dict:
        """Public API: server ``{"ce", "acc"}`` on the public test set."""
        return self._eval_server()

    def _finalize_eval(self, client_eval: Optional[List[Dict]] = None
                       ) -> Dict:
        """Assemble the round metrics dict from per-client metrics (computed
        here if not supplied) plus the server eval and the summary row.
        This is the ONLY place eval results are aggregated — ``run_round``
        and :meth:`evaluate` share it, so the engines cannot drift."""
        out = {"client": (client_eval if client_eval is not None
                          else self._evaluate_clients()),
               "server": self._eval_server()}
        if self._schedule is not None:
            # which registered clients the per-client metrics belong to
            # (sampled rounds measure the round's working set only)
            out["participants"] = [int(j) for j in self._active_ids()]
        cs = out["client"]
        out["summary"] = {
            "avg_acc": float(np.mean([c["acc"] for c in cs])),
            "best_acc": float(np.max([c["acc"] for c in cs])),
            "worst_acc": float(np.min([c["acc"] for c in cs])),
            "avg_ce": float(np.mean([c["ce"] for c in cs])),
            "server_acc": out["server"]["acc"],
            "server_ce": out["server"]["ce"],
        }
        return out

    def evaluate(self) -> Dict:
        """Test CE + template accuracy per device and for the server
        unified model, on the CURRENT parameters (between rounds this is
        post-redistribution, unlike ``run_round``'s post-AMT client
        metrics).  Same code path as ``run_round``'s metrics
        (:meth:`_finalize_eval`)."""
        return self._finalize_eval()

    def _eval_model(self, step, params, data, mask,
                    batch_size: Optional[int] = None) -> Dict:
        """Reference evaluation of one model: host loop over padded
        ``eval_batches``, accumulating the jitted per-batch masked sums
        (``seccl.make_eval_step``) in f32 — the same sequential addition
        order as the stacked engines' scan, so the engines agree to float
        rounding."""
        sums = {k: np.float32(0.0) for k in seccl.EVAL_SUM_KEYS}
        for batch in eval_batches(data, batch_size or self.cfg.batch_size,
                                  mask):
            out = jax.device_get(step(params, batch))
            for k in sums:
                sums[k] = np.float32(sums[k] + out[k])
        return seccl.metrics_from_sums(sums)
