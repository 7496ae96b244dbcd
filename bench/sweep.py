"""Find the highest request rate a serving cell sustains, by a sweep on the
chip (run once when a cell is defined; the cell then offers a fixed rate).

    python3 bench/sweep.py --workload serve720m.chat --rates 2,3,4,5 \
        --seconds 20 --seed 1

One process builds the engine once and, for each rate, runs the cell's
open loop for ``--seconds`` and drains it.  A rate is sustained where the
backlog does not grow across the window: the TTFT of the window's last
third is not far above its first third's, and no request waits at the
window's close for longer than the window's own p95.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import common, generator, model, serving  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    common.use_compile_cache()
    w, conf, traffic, _ = common.cell(args.workload)
    common.check_device(w["chips"])
    from repro.launch.serve_engine import ServingEngine
    from repro.models.model import build_model
    m = conf["model"]
    wseed = model.np_seed(args.seed, 1)
    flat = serving.weights_fn(m, conf.get("lora_b_std", 0.0))(
        jax.random.key(wseed))
    rates = [float(r) for r in args.rates.split(",")]
    biggest = dict(traffic, rate_per_s=max(rates))
    feats = generator.requests(biggest, 0, args.seconds, m["vocab_size"],
                               m["n_modalities"], m["modality_dim"])["feats"]
    prefix = serving.soft_prompts(flat, m, feats)
    engine = ServingEngine(build_model(serving.program_config(m)),
                           model.nest(flat), serving.engine_config(conf, wseed))
    del flat
    serving.warm(engine, conf, traffic, prefix[0],
                 np.random.default_rng(wseed))
    per = [prefix[i] for i in range(prefix.shape[0])]
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    for k, rate in enumerate(rates):
        t = dict(traffic, rate_per_s=rate)
        reqs = generator.requests(t, model.np_seed(args.seed, 10 + k),
                                  args.seconds, m["vocab_size"],
                                  m["n_modalities"], m["modality_dim"])
        led, rid, window, n_sub = serving.serve_window(
            engine, reqs, per, args.seconds)
        wm = serving.window_metrics(led, reqs, window, n_sub, args.seconds)
        due = reqs["due"]
        t1 = np.array(led.tick_t1)
        in_w = [i for i in range(n_sub) if due[i] < args.seconds]
        ttft = np.array([t1[led.adm[i]] - due[i] if led.adm[i] >= 0
                         else np.inf for i in in_w])
        third = max(1, len(in_w) // 3)
        first, last = np.median(ttft[:third]), np.median(ttft[-third:])
        late_adm = sum(1 for i in in_w if led.adm[i] >= 0
                       and t1[led.adm[i]] > window)
        print(f"rate {rate:g}/s: {len(in_w)} due, ttft p50 "
              f"{np.median(ttft) * 1e3:.1f} p95 "
              f"{common.quantile(list(ttft), 0.95) * 1e3:.1f} ms, first third "
              f"{first * 1e3:.1f} last third {last * 1e3:.1f} ms, admitted "
              f"after the close {late_adm}, itl p95 "
              f"{(common.quantile(wm['gaps'], 0.95) * 1e3) if wm['gaps'] else 0:.1f}"
              f" ms, tokens/s {wm['tokens'] / window:.1f}, late p95 "
              f"{wm['late_p95_s'] * 1e3:.2f} ms", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
