"""Bring-up smoke run of the two products on one TPU chip, at the paper
SLM's published widths.

    python chip_smoke.py             # one chip: the three phases below
    python chip_smoke.py --chips 4   # the four-chip path and its reference
    python chip_smoke.py --reduced   # rehearsal at ModelConfig.reduced()
                                     # widths on any backend (no result line)

One chip (``mlecs-slm-720m`` clients, N=2, and the registry's
``qwen3-1.7b`` as the server LLM with ``connector_dim=1280`` so that it
shares the SLM's latent space; random weights and a synthetic multimodal
corpus, all from ``--seed``):

1. ``fused_round`` — ``FederatedRunner`` with the fused single-jit round,
   three evaluated rounds; every eval loss/metric and every trained leaf
   must be finite.
2. ``split_int8`` — the same federation with ``robust="norm_clip"`` (which
   takes the split schedule) and the int8 error-feedback channel, two
   rounds; the device phase's compiled program must hold the Pallas
   quantize kernels (``tpu_custom_call``).
3. ``serving`` — ``ServingEngine`` over client 0's trained parameters
   (LoRA merged), 4 slots and 8 requests; every request must complete, the
   compiled decode step must hold the paged-attention kernel, and the
   kernel path must give the same greedy tokens as the jnp path (else their
   decode-step logits must agree within ``LOGIT_RTOL``).

``--chips 4`` runs only what exists across chips: N=4 clients with the
fused round on ``make_federated_mesh()`` over four chips, the overlap
engine (``staleness=0``) on the same mesh, and the same federation on a
one-device mesh of chip 0 as the reference they must agree with: exactly
(``ENGINE_ATOL``) off the chip, and on it within ``CHIP_METRIC_ATOL`` on
the round metrics, the largest difference printed with its reason.  The SLM
keeps its published widths there but half its depth (18 of 36 layers): at
full depth the one-chip reference's fused round over four stacked clients
needs more HBM than one chip has (16.84 GB against 15.75 GB when compiled
for a v5e).

Every phase prints one line that starts with ``smoke-timing``: host-clock
seconds spent compiling (XLA compile or persistent-cache load, from JAX's
own compile events) and the rest of the phase.  They are smoke timings, not
measurements.  The last line is the JSON result
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU; without one the script exits non-zero before any phase and prints
no result.  No phase catches its own failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import lora  # noqa: E402
from repro.core.channel import ChannelSpec  # noqa: E402
from repro.core.connector import latent_dim  # noqa: E402
from repro.core.federated import FederatedRunner  # noqa: E402
from repro.core.spec import ClientCohort, FederationSpec  # noqa: E402
from repro.data.synthetic import synthetic_multimodal_corpus  # noqa: E402
from repro.launch.mesh import make_federated_mesh, use_compile_cache  # noqa: E402
from repro.launch.serve_engine import EngineConfig, ServingEngine  # noqa: E402
from repro.models.model import build_model  # noqa: E402

SLM_ID = "mlecs-slm-720m"
LLM_ID = "qwen3-1.7b"
TOKEN_CAP = 50257          # GPT-2 vocabulary of the paper SLM: ids stay below
SEQ_LEN = 48               # corpus sequence length (plus 8 soft tokens)
FOUR_CHIP_LAYERS = 18      # SLM depth on the four-chip path (see docstring)
# agreement of the sharded / overlap rounds with the one-chip reference:
# the bound tests/test_federated_engines.py holds the engines to, which
# the CPU rehearsal of the four-device path meets exactly
ENGINE_ATOL = 1e-5
# on the chip the partitioned and one-chip programs round their bf16
# activations differently (see _agreement); the round metrics (CE in nats,
# accuracies as fractions) must then agree to 0.05 — about 1% of the CE
# after two rounds and under a tenth of what one round moves it
CHIP_METRIC_ATOL = 5e-2
# kernel vs jnp decode-step logits, relative to the largest |logit|: both
# paths accumulate in f32 and round the attention output to bf16 once, so
# they may differ by about one bf16 step (2**-8) per layer output
LOGIT_RTOL = 5e-2
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class PhaseClock:
    """Host-clock split of one phase: ``compile_s`` sums JAX's backend
    compile events (XLA compile or persistent-cache load), ``run_s`` is the
    rest of the wall time (tracing, execution, host work)."""

    def __enter__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        self._t0 = time.perf_counter()
        return self

    def _on(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += secs

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_duration_listener(self._on)

    @property
    def run_s(self) -> float:
        return self.wall_s - self.compile_s


def report(phase: str, clock: PhaseClock, **results) -> None:
    """One line per phase: smoke timings first, then the key results."""
    results["live_gb"] = f"{live_bytes() / 1e9:.2f}"
    res = " ".join(f"{k}={v}" for k, v in results.items())
    print(f"smoke-timing (host clock, not a measurement) phase={phase} "
          f"compile_s={clock.compile_s:.1f} run_s={clock.run_s:.1f} | {res}",
          flush=True)


# ---------------------------------------------------------------------------
# configuration

def model_pair(reduced: bool, slm_layers: int = 0):
    """(client SLM, server LLM): the paper SLM at its published widths and
    qwen3-1.7b with its connector in the SLM's latent space."""
    slm = get_config(SLM_ID)
    if slm_layers:
        slm = dataclasses.replace(slm, n_layers=slm_layers)
    llm = get_config(LLM_ID)
    if reduced:
        slm, llm = slm.reduced(), llm.reduced()
    return slm, dataclasses.replace(llm, connector_dim=latent_dim(slm))


def make_corpus(slm, seed: int):
    vocab = min(TOKEN_CAP, slm.vocab_size)
    return synthetic_multimodal_corpus(
        seed, 256, SEQ_LEN, vocab, n_classes=6,
        n_modalities=slm.n_modalities, modality_dim=slm.modality_dim,
        template_len=8)


def federation(slm, llm, n_clients: int, rounds: int, seed: int, **kw):
    return FederationSpec(
        cohorts=(ClientCohort(model=slm, n_clients=n_clients, name="slm"),),
        server_llm=llm, rounds=rounds, local_steps_ccl=2, local_steps_amt=2,
        server_steps=2, batch_size=8, seed=seed, **kw)


def trained_finite(runner) -> bool:
    """Every trained (LoRA + connector) leaf of the clients and the server
    models is finite."""
    trees = [lora.partition(rt.stacked_params) for rt in runner.cohorts]
    trees += [lora.partition(runner.server_llm),
              lora.partition(runner.server_slm)]
    return all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(trees)
               if jnp.issubdtype(x.dtype, jnp.floating))


def metrics_finite(hist) -> bool:
    vals = [v for h in hist for c in h["client"] + [h["server"]]
            for v in c.values()]
    return bool(np.isfinite(vals).all())


def live_bytes() -> int:
    """Bytes of every device array still alive in this process."""
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def _abstract(tree):
    """Shapes, dtypes and placements of ``tree`` (lowering needs no data,
    and donated arguments are gone once the call returns)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        tree)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"smoke check failed: {what}")


# ---------------------------------------------------------------------------
# phases

def phase_fused(slm, llm, corpus, *, rounds: int, seed: int):
    """Phase 1: the fused single-jit round."""
    with PhaseClock() as clk:
        runner = FederatedRunner(federation(slm, llm, 2, rounds, seed),
                                 corpus)
        _check(runner.jit_cache_sizes().keys() == {"round_fn"},
               "homogeneous mean federation takes the fused round")
        hist = [runner.run_round(evaluate=True) for _ in range(rounds)]
        finite = metrics_finite(hist) and trained_finite(runner)
    s = hist[-1]["summary"]
    report("fused_round", clk, rounds=rounds, clients=2,
           avg_ce=f"{s['avg_ce']:.4f}", avg_acc=f"{s['avg_acc']:.4f}",
           server_ce=f"{s['server_ce']:.4f}",
           server_acc=f"{s['server_acc']:.4f}", finite=finite)
    _check(finite, "fused round: every metric and trained leaf finite")
    runner.close()
    del runner
    gc.collect()            # the next phase needs the device memory back


def phase_split_int8(slm, llm, corpus, *, rounds: int, seed: int,
                     on_tpu: bool):
    """Phase 2: the split schedule with the int8 error-feedback channel.
    Returns client 0's trained parameters for serving."""
    spec = federation(slm, llm, 2, rounds, seed, robust="norm_clip",
                      channel=ChannelSpec(codec="int8", error_feedback=True))
    with PhaseClock() as clk:
        runner = FederatedRunner(spec, corpus)
        phases = runner.jit_cache_sizes()
        _check("device_phase/0" in phases and "server_phase" in phases,
               "norm_clip federation takes the split schedule")
        # keep the device phase's argument shapes to inspect its program
        device_phase, seen = runner._device_phase_fns[0], []

        def spy(*args):
            seen.append(_abstract(args))
            return device_phase(*args)
        runner._device_phase_fns[0] = spy
        hist = [runner.run_round(evaluate=True) for _ in range(rounds)]
        finite = metrics_finite(hist) and trained_finite(runner)
        hlo = device_phase.lower(*seen[-1]).compile().as_text()
        kernel = "tpu_custom_call" in hlo
        comm = runner.comm_stats
        params0 = runner.device_params[0]
    s = hist[-1]["summary"]
    report("split_int8", clk, rounds=rounds, clients=2, codec=comm["codec"],
           uplink_bytes=comm["uplink_bytes"],
           uplink_ratio=f"{comm['uplink_ratio']:.2f}",
           avg_ce=f"{s['avg_ce']:.4f}", server_ce=f"{s['server_ce']:.4f}",
           finite=finite, quantize_kernel_in_device_phase=kernel)
    _check(finite, "split int8 round: every metric and trained leaf finite")
    _check(kernel or not on_tpu,
           "Pallas quantize kernels in the compiled device phase")
    runner.close()
    del runner, seen, spy
    gc.collect()
    return params0


def phase_serving(slm, params, *, n_requests: int, seed: int, on_tpu: bool):
    """Phase 3: continuous batching over the paged KV cache, the Pallas
    decode kernel against the jnp gather path."""
    bundle = build_model(slm)
    rng = np.random.default_rng(seed)
    vocab = min(TOKEN_CAP, slm.vocab_size)
    reqs = [(rng.integers(2, vocab, size=int(rng.integers(16, 101)))
             .astype(np.int32), int(rng.integers(8, 33)))
            for _ in range(n_requests)]
    econf = EngineConfig(n_slots=4, page_size=16, n_pages=64,
                         max_pages_per_seq=16, max_out=32,
                         buckets=(16, 32, 64, 128), use_kernel=True,
                         seed=seed)
    with PhaseClock() as clk:
        kern = ServingEngine(bundle, params, econf)
        del params
        rids = [kern.submit(t, max_new=m) for t, m in reqs]
        # the state after the first wave of admissions: the decode step
        # both attention paths are compared on if their tokens differ
        kern._try_admit()
        first = (kern.pstate, kern.sched)
        done_k = kern.run()
        hlo = kern._step.lower(kern.params, kern.pstate,
                               kern.sched).compile().as_text()
        kernel = "tpu_custom_call" in hlo
        ref = ServingEngine(bundle, kern.params,
                            dataclasses.replace(econf, use_kernel=False),
                            merge=False)
        rids_ref = [ref.submit(t, max_new=m) for t, m in reqs]
        done_j = ref.run()
        outs_k = [done_k[r].out for r in rids]
        outs_j = [done_j[r].out for r in rids_ref]
    complete = all(o is not None and len(o) == m
                   for o, (_, m) in zip(outs_k, reqs))
    same = all(np.array_equal(a, b) for a, b in zip(outs_k, outs_j))
    n_tok = sum(len(o) for o in outs_k)
    report("serving", clk, requests=f"{len(done_k)}/{n_requests}",
           tokens=n_tok, decode_steps=kern.n_steps,
           paged_kernel_in_decode_step=kernel, greedy_tokens_equal=same)
    _check(complete and len(done_k) == n_requests,
           "every request completed with its full budget")
    _check(kernel or not on_tpu, "Pallas paged kernel in the decode step")
    if not same:
        i = next(i for i, (a, b) in enumerate(zip(outs_k, outs_j))
                 if not np.array_equal(a, b))
        t = int(np.argmax(outs_k[i] != outs_j[i]))
        print(f"first divergence: request {i} token {t}: kernel "
              f"{outs_k[i][t]} vs jnp {outs_j[i][t]}", flush=True)
        pstate, sd = first
        act = np.asarray(sd["active"])

        def step_logits(use_kernel):
            fn = jax.jit(lambda p, ps, s: bundle.decode_paged(
                p, ps, s["block_tables"], s["seq_lens"],
                s["last_tok"][:, None], s["active"], use_kernel)[0])
            return np.asarray(fn(ref.params, pstate, sd), np.float32)[act]
        lk, lj = step_logits(True), step_logits(False)
        diff, scale = float(np.abs(lk - lj).max()), float(np.abs(lj).max())
        print(f"first decode step logits: max|kernel - jnp|={diff:.3e} "
              f"max|logit|={scale:.3e} rtol={LOGIT_RTOL:g}", flush=True)
        _check(diff <= LOGIT_RTOL * scale,
               f"decode-step logits within {LOGIT_RTOL} x max|logit|")


def run_one_chip(*, reduced: bool, seed: int = 0, rounds=(3, 2),
                 n_requests: int = 8, on_tpu: bool = True) -> None:
    slm, llm = model_pair(reduced)
    corpus = make_corpus(slm, seed)
    phase_fused(slm, llm, corpus, rounds=rounds[0], seed=seed)
    params0 = phase_split_int8(slm, llm, corpus, rounds=rounds[1],
                               seed=seed, on_tpu=on_tpu)
    phase_serving(slm, params0, n_requests=n_requests, seed=seed,
                  on_tpu=on_tpu)


# ---------------------------------------------------------------------------
# four chips

def _federate(name, spec, corpus, *, mesh, engine, rounds):
    """Run one placement of the four-chip comparison; host copies of its
    round summaries and final client LoRA state."""
    with PhaseClock() as clk:
        runner = FederatedRunner(spec, corpus, mesh=mesh, engine=engine)
        hist = [runner.run_round(evaluate=True) for _ in range(rounds)]
        runner.drain()
        state = {k: np.asarray(v, np.float32) for k, v in lora.partition(
            runner.cohorts[0].stacked_params, lora.is_lora_leaf).items()}
        finite = metrics_finite(hist) and trained_finite(runner)
    s = hist[-1]["summary"]
    report(name, clk, engine=engine, chips=int(mesh.devices.size),
           rounds=rounds, clients=spec.n_devices,
           avg_ce=f"{s['avg_ce']:.6f}", server_ce=f"{s['server_ce']:.6f}",
           finite=finite)
    _check(finite, f"{name}: every metric and trained leaf finite")
    runner.close()
    del runner
    gc.collect()
    return [h["summary"] for h in hist], state


def _agreement(name, got, ref, *, on_tpu: bool) -> None:
    """Compare one placement with the one-chip reference: every round
    summary metric and the final client LoRA state."""
    (summ, state), (ref_summ, ref_state) = got, ref
    per_round = [max((abs(a[k] - b[k]), k) for k in a)
                 for a, b in zip(summ, ref_summ)]
    d_sum, key = max(per_round)
    d_state = max(float(np.abs(state[k] - ref_state[k]).max())
                  for k in state)
    lora_max = max(float(np.abs(v).max()) for v in ref_state.values())
    exact = max(d_sum, d_state) <= ENGINE_ATOL
    rounds = " ".join(f"{d:.3e}" for d, _ in per_round)
    print(f"agreement {name} vs reference_1chip: max|d summary| per round="
          f"[{rounds}] (largest: {key}) max|d client LoRA|={d_state:.3e} "
          f"(max|LoRA|={lora_max:.3e}) atol={ENGINE_ATOL:g} within={exact}",
          flush=True)
    if exact:
        return
    _check(on_tpu, f"{name} agrees with the one-chip reference exactly off "
                   "the chip (one partitioner, same per-client arithmetic)")
    print(f"largest difference {max(d_sum, d_state):.3e}: the partitioned "
          "program is compiled apart from the one-chip program, and XLA:TPU "
          "fuses and tiles its bf16 matmuls differently, so activations "
          "round differently from the first step on and Adam's normalised "
          "updates carry that into the adapters; chip bound on the round "
          f"metrics: {CHIP_METRIC_ATOL:g}", flush=True)
    _check(d_sum <= CHIP_METRIC_ATOL,
           f"{name}: round metrics within {CHIP_METRIC_ATOL} of the one-chip "
           "reference")


def run_four_chips(*, reduced: bool, seed: int = 0, rounds: int = 2,
                   on_tpu: bool = True) -> None:
    devs = jax.devices()
    _check(len(devs) >= 4, f"four devices (found {len(devs)})")
    slm, llm = model_pair(reduced, slm_layers=FOUR_CHIP_LAYERS)
    corpus = make_corpus(slm, seed)
    spec = federation(slm, llm, 4, rounds, seed)
    one = jax.sharding.Mesh(np.array(devs[:1]).reshape(1, 1),
                            ("data", "model"))
    ref = _federate("reference_1chip", spec, corpus, mesh=one,
                    engine="vectorized", rounds=rounds)
    for name, engine in (("fused_4chip", "vectorized"),
                         ("overlap_4chip", "overlap")):
        got = _federate(name, spec, corpus, mesh=make_federated_mesh(),
                        engine=engine, rounds=rounds)
        _agreement(name, got, ref, on_tpu=on_tpu)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the four-chip path")
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at ModelConfig.reduced() widths on any "
                         "backend; prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not (on_tpu or args.reduced):
        print(f"no TPU: JAX found {devs[0].platform} devices; nothing run",
              file=sys.stderr)
        return 1
    if args.chips == 4:
        run_four_chips(reduced=args.reduced, seed=args.seed, on_tpu=on_tpu)
    else:
        run_one_chip(reduced=args.reduced, seed=args.seed, on_tpu=on_tpu)
    if args.reduced:
        print("reduced-width rehearsal passed; no device result", flush=True)
        return 0
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
