"""Benchmark harness — one module per paper table/figure + kernel
microbench.  Prints ``name,metric,derived`` CSV rows.

Each benchmark runs in its OWN subprocess: the XLA CPU JIT accumulates
compiled dylibs per process and a full federated sweep exhausts its budget
("Failed to materialize symbols") if everything shares one runtime.

  PYTHONPATH=src python -m benchmarks.run            # fast (CI) mode
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweeps
  PYTHONPATH=src python -m benchmarks.run --only fig3
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

BENCHES = ["table1", "table2", "fig3", "fig4", "gram_ablation",
           "robustness", "population", "microbench"]
_MODULES = {
    "table1": "table1_performance",
    "table2": "table2_scalability",
    "fig3": "fig3_communication",
    "fig4": "fig4_ablation",
    "gram_ablation": "gram_ablation",
    "robustness": "robustness",
    "population": "population_scaling",
    "microbench": "microbench",
}

# benchmarks/*.py that are legitimately NOT registered benchmarks — the
# bench-registry lint rule requires every runnable module to be in
# _MODULES or listed here explicitly
EXCLUDED = {"run", "common"}

_SNIPPET = """
from benchmarks import {mod} as M
table = M.run(fast={fast})
print("CSV_BEGIN")
print(chr(10).join(M.rows_csv(table)))
"""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", choices=BENCHES)
    args = ap.parse_args()

    names = [args.only] if args.only else BENCHES
    csv_rows = ["name,metric,derived"]
    failed = []
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    for name in names:
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        code = _SNIPPET.format(mod=_MODULES[name], fast=not args.full)
        try:
            out = subprocess.run([sys.executable, "-c", code], env=env,
                                 capture_output=True, text=True,
                                 timeout=3600)
            body = out.stdout
            print(body.split("CSV_BEGIN")[0], end="")
            if out.returncode != 0:
                print(out.stderr[-2000:])
                failed.append(name)
            elif "CSV_BEGIN" in body:
                csv_rows.extend(
                    r for r in body.split("CSV_BEGIN", 1)[1].splitlines()
                    if r.strip())
            print(f"=== {name} done in {time.time() - t0:.1f}s ===\n",
                  flush=True)
        except subprocess.TimeoutExpired:
            failed.append(name)
            print(f"=== {name} TIMEOUT ===\n", flush=True)
    print("\n".join(csv_rows))
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
