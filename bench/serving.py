"""Serving cells: ``ServingEngine`` under an open-loop request mix.

Set-up makes the weights and every request's soft prompt from the seed,
builds the engine and warms the prefill bucket, insert and decode programs
this cell's traffic reaches.  The window submits each request when it is
due and ticks the engine; the harness keeps the due times and the host time
of every tick, and reads from the engine's public ``pending`` / ``finished``
state which ticks admitted and finished which request:

- TTFT of a request: from its due time to the end of the tick that
  admitted it (its first token is sampled during admission);
- the inter-token gaps of a request: between the ends of consecutive ticks
  in which it held a decode slot;
- tokens per second: tokens whose tick ended in the window, over the
  window.

The harness also reads which decode slot each admitted request took and,
before every tick, whether the head of the queue had a free slot; where it
stays queued through that tick, its admission waited on free pages.

After the window the engine is drained, its state freed, and the plain
reference (the model's reference module, ``bench.model.backbone``) scores a
sample of the finished requests, the longest and one from every slot among
them: the widest gap by which a served token's logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, generator, model

DRAIN_S = 120.0           # a request due in the window may finish this late
REF_BATCH = 4             # sequences per reference call


def engine_config(conf: dict, seed: int):
    from repro.launch.serve_engine import EngineConfig
    return EngineConfig(**conf["engine"], seed=seed)


def program_config(m: dict):
    from repro.configs.base import ModelConfig
    keys = ModelConfig.__dataclass_fields__
    return ModelConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                          for k, v in m.items() if k in keys})


def weights_fn(m: dict, lora_b_std: float):
    """One jitted call: seed -> flat weights, in the served type."""
    return jax.jit(lambda k: model.draw_model(k, m, lora_b_std))


def soft_prompts(flat, m, feats):
    """Every request's soft prompt (n, n_soft, d), made on the device from
    its modality features through the connector, in the served type."""
    cp = {k.split("/", 1)[1]: v for k, v in flat.items()
          if k.startswith("connector/")}

    @jax.jit
    def f(cp, x):
        soft, _, _ = model.connector(cp, m, x, jnp.ones(x.shape[:2], bool))
        return soft.astype(jnp.dtype(m["dtype"]))
    return f(cp, jnp.asarray(feats))


def _buckets_hit(buckets, lo: int, hi: int) -> list:
    """(bucket, a prompt length that lands in it) for every bucket the
    prompt-length range [lo, hi] reaches."""
    out, prev = [], 0
    for b in sorted(buckets):
        if b >= lo and prev < hi:
            out.append((b, max(lo, prev + 1)))
        prev = b
    return out


def warm(engine, conf, traffic, prefix0, rng) -> None:
    """Compile, before the window, every program the cell's traffic will
    run: each prefill bucket its prompts reach (with its insert), and where
    requests decode, the decode step with every slot in use, and the host
    side's read-back of the finished slots for each number of slots that
    can finish in one step (one small program per count)."""
    m, ec = conf["model"], conf["engine"]
    lo, hi = generator.length_range(traffic["prompt_len"])
    new = min(2, generator.length_range(traffic["output_len"])[1])
    hits = _buckets_hit(ec["buckets"], lo, hi)

    def submit(L):
        engine.submit(rng.integers(2, m["vocab_size"], L).astype(np.int32),
                      max_new=new, prefix_embeds=prefix0)
    for _, L in hits:
        submit(L)
        engine.run()
    if new >= 2:
        for _ in range(ec["n_slots"]):
            submit(hits[0][1])
        engine.run()
        for c in range(1, ec["n_slots"] + 1):
            np.array(engine.sched["out_buf"][jnp.asarray(np.arange(c))])


class Ledger:
    """The harness's record of one window: due times, ticks, admissions and
    finishes, all on the host clock relative to the window's start."""

    def __init__(self, n: int):
        self.sub = np.full(n, np.nan)       # submit time
        self.adm = np.full(n, -1)           # tick that admitted it
        self.fin = np.full(n, -1)           # tick that finished it
        self.slot = np.full(n, -1)          # decode slot it held
        self.page_wait = []                 # (tick, request) left queued
        self.tick_t0, self.tick_t1 = [], []
        self.t0 = 0.0


def serve_window(engine, reqs, prefix, seconds, prof=None):
    """Drive the open loop for ``seconds``, then drain.  Returns the
    Ledger and the rid of each request (index order)."""
    n = len(reqs["due"])
    led = Ledger(n)
    rid = [None] * n
    idx_of = {}
    in_slot = set()
    waiting = set()
    clock = time.perf_counter
    nxt = 0
    t0 = led.t0 = clock()
    # the traced slice ends with the window: stopping the profiler stalls
    # the host for seconds, which must not hold up the open loop
    trace_at = max(0.0, seconds - min(6.0, seconds * 0.3))

    def tick():
        tk = len(led.tick_t0)
        head = (engine.pending[0].rid
                if engine.pending and engine._free_slots else None)
        led.tick_t0.append(clock() - t0)
        with common.span("tick"):
            engine.tick()
        led.tick_t1.append(clock() - t0)
        if head is not None and engine.pending \
                and engine.pending[0].rid == head:
            led.page_wait.append((tk, idx_of[head]))
        still = {r.rid for r in engine.pending}
        slot_of = {r.rid: s for s, r in engine._slot_req.items()}
        for r in list(waiting):
            if r not in still:
                waiting.discard(r)
                i = idx_of[r]
                led.adm[i] = tk
                if r in engine.finished:
                    led.fin[i] = tk
                else:
                    in_slot.add(r)
                    led.slot[i] = slot_of[r]
        for r in [r for r in in_slot if r in engine.finished]:
            in_slot.discard(r)
            led.fin[idx_of[r]] = tk

    while True:
        now = clock() - t0
        if prof is not None and prof.t0 is None and now >= trace_at:
            prof.start()
        while nxt < n and reqs["due"][nxt] <= min(now, seconds):
            with common.span("submit"):
                r = engine.submit(reqs["tokens"][nxt],
                                  max_new=int(reqs["max_new"][nxt]),
                                  prefix_embeds=prefix[nxt])
            led.sub[nxt] = clock() - t0
            rid[nxt], idx_of[r] = r, nxt
            waiting.add(r)
            nxt += 1
        if now >= seconds:
            break
        if engine.busy:
            tick()
        else:
            wake = min(reqs["due"][nxt] if nxt < n else seconds, seconds)
            with common.span("wait"):
                time.sleep(max(0.0, wake - (clock() - t0)))
    window = clock() - t0      # the profiler's stop is no part of it
    if prof is not None:
        prof.stop()
    drain_end = clock() + DRAIN_S
    while engine.busy and clock() < drain_end:
        tick()
    return led, rid, window, nxt


def window_metrics(led: Ledger, reqs, window: float, n_sub: int,
                   seconds: float) -> dict:
    """TTFT of every request due in the window (``seconds`` long), the
    inter-token gaps and tokens of the ticks that ended in it (the window
    closes ``window`` after its start, at the end of the tick running when
    ``seconds`` had passed)."""
    due = reqs["due"]
    t1 = np.array(led.tick_t1)
    in_win = np.flatnonzero(due < seconds)
    ttft, gaps, tokens, failed = [], [], 0, 0
    for i in in_win:
        a, f, m = led.adm[i], led.fin[i], int(reqs["max_new"][i])
        if a < 0 or f < 0:
            failed += 1
            ttft.append(math.inf)
            continue
        ttft.append(t1[a] - due[i])
    for i in range(n_sub):
        a, f, m = led.adm[i], led.fin[i], int(reqs["max_new"][i])
        if a < 0:
            continue
        # tokens 1 and 2 come out of the admitting tick (prefill, then its
        # decode step), one more from each later tick the request holds
        last = f if f >= 0 else len(t1) - 1
        times = [t1[a]] * min(m, 2) + [t1[k] for k in range(a + 1, last + 1)]
        tokens += sum(1 for t in times[:m] if t <= window)
        for k in range(a + 1, last + 1):
            if t1[k] <= window:
                gaps.append(t1[k] - t1[k - 1])
    late = led.sub[:n_sub] - due[:n_sub]
    waits = [(k, i) for k, i in led.page_wait if t1[k] <= window]
    return {"ttft": ttft, "gaps": gaps, "tokens": tokens,
            "attempted": len(in_win), "failed": failed,
            "late_p95_s": common.quantile(list(late), 0.95),
            "page_wait_ticks": len(waits),
            "page_wait_requests": len({i for _, i in waits})}


def sample_for_check(rng, led, reqs, seconds, budget: int) -> list:
    """Finished requests due in the window to compare: the longest, one
    drawn from the seed for every decode slot that served any, then others
    drawn from the seed until ``budget`` served tokens."""
    ok = [int(i) for i in np.flatnonzero(reqs["due"] < seconds)
          if led.fin[i] >= 0]
    if not ok:
        return []
    pick = [max(ok, key=lambda i: (int(reqs["max_new"][i]), -i))]
    for s in sorted({int(led.slot[i]) for i in ok} - {-1}):
        held = [i for i in ok if led.slot[i] == s and i not in pick]
        if held and int(led.slot[pick[0]]) != s:
            pick.append(int(rng.choice(held)))
    total = sum(int(reqs["max_new"][i]) for i in pick)
    for i in rng.permutation([i for i in ok if i not in pick]):
        if total >= budget:
            break
        pick.append(int(i))
        total += int(reqs["max_new"][i])
    return pick


def reference_gaps(flat, m, reqs, prefix, outs, pick, prec_ctl=None):
    """Widest gap (over the picked requests' served tokens) between the
    reference's best logit and the served token's logit.  With
    ``prec_ctl``, also the widest gap of the token that reference in that
    precision puts first (the control)."""
    p = model.nest({k: v for k, v in flat.items()
                    if not k.startswith("connector/")})
    ref = model.backbone(m)
    max_p = max(int(reqs["prompt_len"][i]) for i in pick)
    max_o = max(len(outs[i]) for i in pick)
    seq = max_p + max_o
    seq = int(2 ** math.ceil(math.log2(max(seq, 16))))

    def logits_fn(prec):
        return jax.jit(lambda p, toks, pre, where: ref.forward_at(
            p, m, toks, pre, where, prec))

    f_ref = logits_fn("f32")
    f_ctl = logits_fn(prec_ctl) if prec_ctl else None
    worst, worst_ctl = 0.0, 0.0
    for b in range(0, len(pick), REF_BATCH):
        chunk = pick[b:b + REF_BATCH]
        toks = np.zeros((REF_BATCH, seq), np.int32)
        where = np.zeros((REF_BATCH, max_o), np.int32)
        served = np.zeros((REF_BATCH, max_o), np.int32)
        valid = np.zeros((REF_BATCH, max_o), bool)
        pre = np.zeros((REF_BATCH,) + prefix.shape[1:], np.float32)
        P = prefix.shape[1]
        for r, i in enumerate(chunk):
            S, out = int(reqs["prompt_len"][i]), outs[i]
            full = np.concatenate([reqs["tokens"][i], out[:-1]])
            toks[r, :len(full)] = full
            where[r, :len(out)] = P + S - 1 + np.arange(len(out))
            served[r, :len(out)] = out
            valid[r, :len(out)] = True
            pre[r] = np.asarray(prefix[i], np.float32)
        args = (p, jnp.asarray(toks), jnp.asarray(pre), jnp.asarray(where))
        lg = np.asarray(f_ref(*args))
        best = lg.max(-1)
        tok_lg = np.take_along_axis(lg, served[..., None], -1)[..., 0]
        gap = np.where(valid, best - tok_lg, 0.0)
        worst = max(worst, float(gap.max()))
        if f_ctl is not None:
            first = np.asarray(f_ctl(*args)).argmax(-1)
            ctl = best - np.take_along_axis(lg, first[..., None], -1)[..., 0]
            worst_ctl = max(worst_ctl, float(np.where(valid, ctl, 0).max()))
    return worst, worst_ctl


def run(w, conf, traffic, limits, seed, seconds, trace, devs, t_start,
        faults=(), prec_ctl=None):
    """One run of a serving cell.  Returns (result dict, checks)."""
    from repro.launch.serve_engine import ServingEngine
    from repro.models.model import build_model

    m = conf["model"]
    counter = common.CompileCounter()
    wseed = model.np_seed(seed, 1)
    reqs = generator.requests(traffic, model.np_seed(seed, 2), seconds,
                              m["vocab_size"], m["n_modalities"],
                              m["modality_dim"])
    draw = weights_fn(m, conf.get("lora_b_std", 0.0))
    flat = draw(jax.random.key(wseed))
    prefix = soft_prompts(flat, m, reqs["feats"])
    prefix_host = np.asarray(prefix.astype(jnp.float32))
    params = model.nest(flat)
    del flat
    bundle = build_model(program_config(m))
    engine = ServingEngine(bundle, params, engine_config(conf, wseed))
    del params
    warm(engine, conf, traffic, prefix[0], np.random.default_rng(wseed))
    per_req = [prefix[i] for i in range(len(reqs["due"]))]
    jax.block_until_ready(per_req)
    n_compile_setup = counter.n
    prof = common.Profiler(bool(trace), w["name"])
    setup_s = time.perf_counter() - t_start
    led, rid, window, n_sub = serve_window(
        engine, reqs, per_req, seconds, prof if trace else None)
    n_compile_window = counter.n - n_compile_setup
    counter.close()
    outs = {i: engine.finished[rid[i]].out for i in range(n_sub)
            if rid[i] is not None and rid[i] in engine.finished}
    dev = common.device_info(devs)
    del engine, prefix, per_req
    gc.collect()
    if "token" in faults:          # a served token altered where it came out
        i = max(outs, key=lambda i: len(outs[i]))
        outs[i] = outs[i].copy()
        outs[i][len(outs[i]) // 2] = (outs[i][len(outs[i]) // 2] + 7) % \
            m["vocab_size"]
    wm = window_metrics(led, reqs, window, n_sub, seconds)
    ttft_p95_ms = common.quantile(wm["ttft"], 0.95) * 1e3
    print(f"set-up {setup_s:.3f} s; generator: {n_sub} requests submitted, "
          f"p95 lateness {wm['late_p95_s'] * 1e3:.3f} ms; ttft p95 "
          f"{ttft_p95_ms:.3f} ms; {wm['tokens'] / window:.3f} tokens/s; "
          f"{len(led.tick_t1)} ticks; compiles in window {n_compile_window}; "
          f"admissions that "
          f"waited on free pages in the window: {wm['page_wait_requests']} "
          f"requests, {wm['page_wait_ticks']} ticks",
          file=sys.stderr, flush=True)

    # correctness, after the window, with the program's state freed
    rng = np.random.default_rng(model.np_seed(seed, 3))
    pick = sample_for_check(rng, led, reqs, seconds,
                            limits["checked_tokens"])
    t_ref = time.perf_counter()
    flat = draw(jax.random.key(wseed))
    gap, gap_ctl = reference_gaps(flat, m, reqs, prefix_host, outs, pick,
                                  prec_ctl)
    del flat
    print(f"reference: {len(pick)} requests from "
          f"{len({int(led.slot[i]) for i in pick})} slots in "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr, flush=True)
    # in a control run the control stands in the program's place
    shown = gap_ctl if prec_ctl else gap
    checks = [("logit_gap", shown, limits["logit_gap"]),
              ("checked_tokens", int(sum(len(outs[i]) for i in pick)),
               limits["checked_tokens"]),
              ("compiles_in_window", n_compile_window, 0)]
    if prec_ctl:
        checks.append(("program_logit_gap", gap, limits["logit_gap"]))
    correct = (shown <= limits["logit_gap"]
               and checks[1][1] >= limits["checked_tokens"]
               and wm["failed"] == 0)

    if trace:
        metrics = trace_metrics(w, conf, traffic, prof, led, reqs, window,
                                n_sub, dev, wm)
        dev.update(metrics.pop("_device"))
        breakdown = metrics.pop("_breakdown")
    else:
        metrics = common.end_to_end(w["name"], {
            "setup_s": setup_s,
            "itl_p95_ms": (common.quantile(wm["gaps"], 0.95) * 1e3
                           if wm["gaps"] else None)})
        breakdown = None
    res = {"correct": bool(correct), "attempted": wm["attempted"],
           "failed": wm["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        res["breakdown"] = breakdown
    return res, checks


def trace_metrics(w, conf, traffic, prof, led, reqs, window, n_sub, dev,
                  wm):
    """Per-layer metrics of the traced slice of the window, and those of
    the whole window's record (``wm``, from :func:`window_metrics`)."""
    from bench import trace as trace_lib
    path = prof.xplane()
    summary = trace_lib.summarize(path, prof) if path else None
    ctx = {"cell": w["name"], "conf": conf, "traffic": traffic,
           "trace": summary, "ledger": led, "reqs": reqs,
           "window": window, "n_sub": n_sub, "device_kind": dev["kind"],
           "chips": dev["count"],
           "window_metrics": wm,
           "trace_span": ((prof.t0 - led.t0, prof.t1 - led.t0)
                          if prof.t0 else None)}
    return common.collect_per_layer(w, ctx, summary)


def traced_ticks(ctx) -> list:
    """Ticks that began and ended inside the traced slice, each as
    (admitted request indices, [(request index, cached entries incl. the
    new token) of every slot its decode step advanced])."""
    led, reqs = ctx["ledger"], ctx["reqs"]
    if ctx.get("trace_span") is None:
        return []
    a, b = ctx["trace_span"]
    P = ctx["conf"]["model"]["n_soft_tokens"]
    ks = [k for k in range(len(led.tick_t0))
          if led.tick_t0[k] >= a and led.tick_t1[k] <= b]
    out = []
    for k in ks:
        adm = [int(i) for i in np.flatnonzero(led.adm == k)]
        live = []
        for i in np.flatnonzero((led.adm >= 0) & (led.adm <= k)
                                & ((led.fin >= k) | (led.fin < 0))):
            if int(reqs["max_new"][i]) < 2:
                continue
            ctx_len = P + int(reqs["prompt_len"][i]) + (k - led.adm[i]) + 1
            live.append((int(i), ctx_len))
        out.append((adm, live))
    return out
