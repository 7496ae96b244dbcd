"""Continuous-batching serving engine over the paged KV cache.

The seed path (``launch.serve.generate``) runs one request batch to
completion: every sequence holds a private contiguous cache sized for the
longest request, the batch recompiles when its shape changes, and a finished
sequence keeps burning decode FLOPs until the *last* one finishes.  This
engine replaces that with the vLLM-style serving loop on top of
``repro.models.paged``:

* **Fixed decode slots** — ``n_slots`` sequences decode together in ONE
  jitted step (token sampling, paged cache write, done-mask update and slot
  release all inside the jit; no per-token Python dispatch).
* **Paged KV pool + free-list allocator** — requests own pages, not a
  contiguous region; admission only needs ``ceil(ctx / page_size)`` free
  pages, and eviction returns them the moment a sequence finishes.  The
  step and the insert take the pool state donated (:func:`jit_step`,
  :func:`jit_insert`), so each writes the one pool in place: hold no
  reference to ``engine.pstate`` across a tick.
* **Admission control** — pending requests are admitted whenever a slot AND
  enough pages are free; prompts are right-padded to compile buckets for the
  attention families (recurrent families prefill at exact length — padding
  would be folded into the SSM state).
* **Mid-flight eviction** — a sequence that hits its budget (or ``eos_id``)
  has its block-table row zeroed *inside the jit* (subsequent unconditional
  cache writes land on scratch page 0) and its pages freed on the host, so
  the next pending request takes over the slot while neighbours keep
  decoding.

``CohortServer`` lifts this to a heterogeneous :class:`FederationSpec`
checkpoint set: one engine (one compiled decode) per cohort architecture,
ticked round-robin so all cohorts make progress concurrently — the paper's
"different edge domains deploy different backbones" serving story.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lora
from repro.models import paged
from repro.models.model import ModelBundle, build_model

_RID = itertools.count()

# Host spans on the profiler's clock (about a microsecond each while no trace
# runs): ``serve.tick`` ⊃ ``serve.admit`` (⊃ ``serve.prefill``,
# ``serve.insert``), ``serve.step``, ``serve.finish``.  Their arguments are
# host state; none reads a device array.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 8                 # concurrent decode lanes (ONE jit trace)
    page_size: int = 16              # cache entries per page
    n_pages: int = 128               # physical pool (page 0 = scratch)
    max_pages_per_seq: int = 16      # block-table width
    max_out: int = 64                # output buffer capacity per slot
    temperature: float = 0.0         # 0 = greedy (argmax inside the jit)
    eos_id: int = -1                 # -1 = never stop early
    buckets: Tuple[int, ...] = (16, 32, 64, 128)   # prefill compile buckets
    use_kernel: Optional[bool] = None  # None = Pallas kernel on TPU,
                                       # jnp gather path elsewhere
    seed: int = 0

    def __post_init__(self):
        if self.page_size < 1 or self.n_pages < 2:
            raise ValueError("need page_size >= 1 and n_pages >= 2 "
                             "(page 0 is the scratch page)")
        if self.max_pages_per_seq * self.page_size < max(self.buckets):
            raise ValueError("max_pages_per_seq * page_size must cover the "
                             "largest prefill bucket")


@dataclasses.dataclass
class Request:
    tokens: np.ndarray               # (S,) int32 prompt
    max_new: int = 16
    frontend_embeds: Optional[np.ndarray] = None   # (T, F) vlm/encdec stub
    prefix_embeds: Optional[np.ndarray] = None     # (P, d) ML-ECS soft prompt
    rid: int = dataclasses.field(default_factory=lambda: next(_RID))
    # filled by the engine, on the ``time.perf_counter`` clock
    t_submit: float = 0.0
    t_admit: float = 0.0             # its admission starts
    t_first: float = 0.0             # its first token is in place
    t_done: float = 0.0
    out: Optional[np.ndarray] = None

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


def init_sched(ec: EngineConfig) -> dict:
    """The scheduler's device state: one row per decode slot."""
    return {
        "block_tables": jnp.zeros((ec.n_slots, ec.max_pages_per_seq),
                                  jnp.int32),
        "seq_lens": jnp.zeros((ec.n_slots,), jnp.int32),
        "active": jnp.zeros((ec.n_slots,), bool),
        "last_tok": jnp.zeros((ec.n_slots,), jnp.int32),
        "out_buf": jnp.zeros((ec.n_slots, ec.max_out), jnp.int32),
        "n_out": jnp.zeros((ec.n_slots,), jnp.int32),
        "budget": jnp.zeros((ec.n_slots,), jnp.int32),
        "key": jax.random.key(ec.seed),
    }


def jit_step(bundle: ModelBundle, ec: EngineConfig):
    """The ONE jitted decode step, ``(params, pstate, sched) -> (pstate,
    sched)``.  ``pstate`` is donated, so the page pool is written in place;
    ``sched`` is not (the host reads it between ticks)."""
    n = ec.n_slots

    def step(params, pstate, sd):
        logits, pstate = bundle.decode_paged(
            params, pstate, sd["block_tables"], sd["seq_lens"],
            sd["last_tok"][:, None], sd["active"], ec.use_kernel)
        if ec.temperature > 0:
            key, sub = jax.random.split(sd["key"])
            tok = jax.random.categorical(sub, logits / ec.temperature,
                                         axis=-1)
        else:
            key, tok = sd["key"], jnp.argmax(logits, axis=-1)
        tok = tok.astype(jnp.int32)
        act = sd["active"]
        row = jnp.arange(n)
        idx = jnp.minimum(sd["n_out"], ec.max_out - 1)
        out_buf = sd["out_buf"].at[row, idx].set(
            jnp.where(act, tok, sd["out_buf"][row, idx]))
        n_out = sd["n_out"] + act.astype(jnp.int32)
        seq_lens = sd["seq_lens"] + act.astype(jnp.int32)
        done = act & ((n_out >= sd["budget"]) | (tok == ec.eos_id))
        return pstate, {
            # release: a zeroed row points every future write at the
            # scratch page; the host frees the physical pages
            "block_tables": jnp.where(done[:, None], 0,
                                      sd["block_tables"]),
            "seq_lens": seq_lens,
            "active": act & ~done,
            "last_tok": jnp.where(act, tok, sd["last_tok"]),
            "out_buf": out_buf,
            "n_out": n_out,
            "budget": sd["budget"],
            "key": key,
        }

    return jax.jit(step, donate_argnums=(1,))


def jit_insert(bundle: ModelBundle):
    """The jitted insert, ``(pstate, pack, slot, page_ids) -> pstate``, with
    ``pstate`` donated: a prompt's pages are scattered into the pool in
    place."""
    return jax.jit(bundle.insert_paged, donate_argnums=(0,))


class ServingEngine:
    """Continuous batching for ONE architecture (one compiled decode)."""

    def __init__(self, bundle: ModelBundle, params,
                 econf: Optional[EngineConfig] = None, merge: bool = True):
        self.bundle, self.cfg = bundle, bundle.cfg
        self.econf = ec = econf or EngineConfig()
        self.params = lora.merge_lora(params, bundle.cfg) if merge else params
        self.paged_fam = self.cfg.family != "ssm"
        # recurrent state would integrate padded tokens -> exact lengths
        self.exact_len = self.cfg.family in ("ssm", "hybrid")
        self.pstate = bundle.init_paged(ec.n_slots, ec.n_pages, ec.page_size)
        self.sched = init_sched(ec)
        self.pending: collections.deque = collections.deque()
        self.finished: Dict[int, Request] = {}
        self._free_pages: List[int] = list(range(ec.n_pages - 1, 0, -1))
        self._free_slots: List[int] = list(range(ec.n_slots))
        self._slot_req: Dict[int, Request] = {}
        self._slot_pages: Dict[int, List[int]] = {}
        self.n_ticks = 0
        self.n_steps = 0
        self.n_admitted = 0
        self.n_page_waits = 0
        self.slot_steps = 0
        self._step = jit_step(bundle, ec)
        self._prefill = jax.jit(bundle.prefill_paged)   # one trace per bucket
        self._insert = jit_insert(bundle)               # one per page count

    # ------------------------------------------------------------------
    # admission

    def submit(self, tokens, max_new: int = 16, frontend_embeds=None,
               prefix_embeds=None) -> int:
        req = Request(np.array(tokens, np.int32).reshape(-1),
                      min(max_new, self.econf.max_out),
                      frontend_embeds, prefix_embeds)
        req.t_submit = time.perf_counter()
        self.pending.append(req)
        return req.rid

    def _prefix_len(self, req: Request) -> int:
        P = 0
        if self.cfg.frontend and self.cfg.family != "encdec":
            P += self.cfg.frontend_tokens
        if req.prefix_embeds is not None:
            P += req.prefix_embeds.shape[0]
        return P

    def _bucket_len(self, n: int) -> int:
        if self.exact_len:
            return n
        for b in sorted(self.econf.buckets):
            if b >= n:
                return b
        return n

    def _sample_host(self, logits):
        """First token comes from the prefill logits (same key stream as the
        jitted step so temperature runs stay reproducible)."""
        ec = self.econf
        if ec.temperature > 0:
            key, sub = jax.random.split(self.sched["key"])
            self.sched = dict(self.sched, key=key)
            return int(jax.random.categorical(sub, logits / ec.temperature))
        return int(jnp.argmax(logits))

    def _try_admit(self) -> int:
        ec = self.econf
        admitted = 0
        while self.pending and self._free_slots:
            req = self.pending[0]
            S = int(req.tokens.shape[0])
            P = self._prefix_len(req)
            S_pad = self._bucket_len(S)
            ctx = P + S_pad + req.max_new
            n_req = paged.pages_for(ctx, ec.page_size) if self.paged_fam else 0
            if ctx > ec.max_pages_per_seq * ec.page_size:
                raise ValueError(
                    f"request needs {ctx} cache entries > block-table "
                    f"capacity {ec.max_pages_per_seq * ec.page_size}")
            if n_req > len(self._free_pages):
                self.n_page_waits += 1
                break                       # wait for an eviction
            self.pending.popleft()
            slot = self._free_slots.pop()
            pages = [self._free_pages.pop() for _ in range(n_req)]
            req.t_admit = time.perf_counter()
            with _span("serve.admit", rid=req.rid, slot=slot, pages=n_req,
                       bucket=S_pad, queued_us=round(
                           (req.t_admit - req.t_submit) * 1e6)):
                self._admit(req, slot, pages, S, P, S_pad)
            admitted += 1
        self.n_admitted += admitted
        return admitted

    def _admit(self, req: Request, slot: int, pages: List[int], S: int,
               P: int, S_pad: int) -> None:
        """Prefill ``req`` (prompt of ``S`` tokens padded to ``S_pad``, after
        ``P`` prefix entries), sample its first token, and place it in decode
        ``slot`` over ``pages`` — or finish it at once."""
        ec = self.econf
        with _span("serve.prefill"):
            toks = np.zeros((1, S_pad), np.int32)
            toks[0, :S] = req.tokens
            batch = {"tokens": jnp.asarray(toks)}
            if req.frontend_embeds is not None:
                batch["frontend_embeds"] = jnp.asarray(
                    req.frontend_embeds)[None]
            if req.prefix_embeds is not None:
                batch["prefix_embeds"] = jnp.asarray(req.prefix_embeds)[None]
            last, pack, _ = self._prefill(self.params, batch, jnp.int32(S))
            tok0 = self._sample_host(last[0])

        if req.max_new <= 1 or tok0 == ec.eos_id:
            self._free_pages.extend(pages)
            self._free_slots.append(slot)
            req.out = np.array([tok0], np.int32)
            req.t_first = req.t_done = time.perf_counter()
            self.finished[req.rid] = req
            return

        with _span("serve.insert"):
            if self.paged_fam:
                n_used = paged.pages_for(P + S_pad, ec.page_size)
                page_ids = jnp.asarray(pages[:n_used], jnp.int32)
            else:
                page_ids = jnp.zeros((0,), jnp.int32)
            self.pstate = self._insert(self.pstate, pack, jnp.int32(slot),
                                       page_ids)
            bt_row = np.zeros((ec.max_pages_per_seq,), np.int32)
            bt_row[:len(pages)] = pages
            sd = self.sched
            self.sched = dict(
                sd,
                block_tables=sd["block_tables"].at[slot].set(
                    jnp.asarray(bt_row)),
                seq_lens=sd["seq_lens"].at[slot].set(P + S),
                active=sd["active"].at[slot].set(True),
                last_tok=sd["last_tok"].at[slot].set(tok0),
                out_buf=sd["out_buf"].at[slot, 0].set(tok0),
                n_out=sd["n_out"].at[slot].set(1),
                budget=sd["budget"].at[slot].set(req.max_new),
            )
            self._slot_req[slot] = req
            self._slot_pages[slot] = pages
        req.t_first = time.perf_counter()

    # ------------------------------------------------------------------
    # the serving loop

    @property
    def busy(self) -> bool:
        return bool(self.pending or self._slot_req)

    def step_once(self):
        """One jitted decode step + host-side collection of finished slots."""
        busy = len(self._slot_req)
        with _span("serve.step", busy=busy):
            prev_active = np.array(self.sched["active"])
            self.pstate, self.sched = self._step(self.params, self.pstate,
                                                 self.sched)
            act = np.array(self.sched["active"])
        self.n_steps += 1
        self.slot_steps += busy
        newly = np.nonzero(prev_active & ~act)[0]
        if len(newly):
            with _span("serve.finish"):
                n_out = np.array(self.sched["n_out"])
                rows = np.array(self.sched["out_buf"][jnp.asarray(newly)])
                for i, slot in enumerate(newly):
                    self._finish(int(slot), rows[i, :n_out[slot]])

    def _finish(self, slot: int, tokens):
        req = self._slot_req.pop(slot)
        req.out = np.array(tokens, np.int32)
        req.t_done = time.perf_counter()
        self.finished[req.rid] = req
        self._free_pages.extend(self._slot_pages.pop(slot))
        self._free_slots.append(slot)

    def tick(self) -> bool:
        """Admit what fits, then decode one step.  Returns ``busy``."""
        with _span("serve.tick", tick=self.n_ticks):
            self.n_ticks += 1
            self._try_admit()
            if self._slot_req:
                self.step_once()
        return self.busy

    def stats(self) -> Dict[str, int]:
        """A snapshot of the scheduler from host state alone (it reads no
        device array): requests ``queued``; decode slots busy and in all;
        pages free and in all (page 0, the scratch page, not counted); and,
        counted since the engine was built, ``admissions``, the admissions
        deferred because free pages ran short (``admit_waits_pages``, one
        per tick at most), decode ``steps``, and ``slot_steps``, the busy
        slots summed over decode steps."""
        ec = self.econf
        return {"queued": len(self.pending),
                "slots_busy": len(self._slot_req),
                "slots_total": ec.n_slots,
                "pages_free": len(self._free_pages),
                "pages_total": ec.n_pages - 1,
                "admissions": self.n_admitted,
                "admit_waits_pages": self.n_page_waits,
                "steps": self.n_steps,
                "slot_steps": self.slot_steps}

    def run(self) -> Dict[int, Request]:
        """Drive everything submitted so far to completion."""
        while self.busy:
            self.tick()
        return self.finished


# ---------------------------------------------------------------------------
# heterogeneous cohorts

class CohortServer:
    """One :class:`ServingEngine` per :class:`FederationSpec` cohort.

    Each cohort architecture gets its own compiled decode (the
    structure-agnostic contract: heterogeneous backbones share the protocol,
    not the trace) and :meth:`serve` ticks the engines round-robin so all
    cohorts decode concurrently."""

    def __init__(self, spec, cohort_params,
                 econf: Optional[EngineConfig] = None, merge: bool = True):
        if len(cohort_params) != spec.n_cohorts:
            raise ValueError(
                f"got {len(cohort_params)} param trees for "
                f"{spec.n_cohorts} cohorts")
        self.spec = spec
        self.engines = [
            ServingEngine(build_model(c.model), p, econf, merge=merge)
            for c, p in zip(spec.cohorts, cohort_params)]

    @classmethod
    def from_spec(cls, spec, econf: Optional[EngineConfig] = None
                  ) -> "CohortServer":
        """Fresh per-cohort checkpoints (connector included when the cohort
        model is multimodal) — the serving-side mirror of the runner's
        per-cohort init."""
        from repro.core import ccl
        params = []
        for c_idx, c in enumerate(spec.cohorts):
            bundle = build_model(c.model)
            k = jax.random.fold_in(jax.random.key(spec.seed), c_idx)
            p = ccl.init_unified(k, bundle) if c.model.n_modalities \
                else bundle.init(k)
            params.append(p)
        return cls(spec, params, econf)

    def submit(self, cohort: int, tokens, **kw) -> int:
        return self.engines[cohort].submit(tokens, **kw)

    def serve(self) -> List[Dict[int, Request]]:
        """Round-robin until every cohort's queue drains."""
        while any(e.busy for e in self.engines):
            for e in self.engines:
                if e.busy:
                    e.tick()
        return [e.finished for e in self.engines]
