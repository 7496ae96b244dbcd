"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its correctness limits are
found by name through ``BENCHMARK.json``.  With ``--trace 0`` the result
line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of a slice of the window.
``--control 1`` puts the lower-precision control in the program's place:
the compared numbers and ``correct`` are then the control's, which must
come out not correct; the program's own readings are printed beside them
(for setting limits; the benchmark's own runs do not use it).  The last line of stdout
is the JSON result; without the accelerator the cell needs, the run exits
non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    common.use_compile_cache()
    w, conf, traffic, limits = common.cell(args.workload)
    try:
        devs = common.check_device(w["chips"])
    except common.NoDevice as e:
        print(f"{e}; nothing run", file=sys.stderr)
        return 3
    if conf["harness"] == "federation":
        from bench import federation as harness
    else:
        from bench import serving as harness
    res, checks = harness.run(w, conf, traffic, limits, args.seed,
                             args.seconds, args.trace, devs, T_START,
                             prec_ctl="fp8" if args.control else None)
    common.report(res, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
