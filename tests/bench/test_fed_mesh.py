"""The four-chip federation cell's path through the harness on four host
CPU devices, at toy widths: the runner gets the program's federated mesh,
the client stack lies one client a device, and the readings the harness
compares match those of the same federation on one device."""
import json
import os
import subprocess
import sys

from bench import common

ENGINE_ATOL = 1e-5   # the sharded engines' agreement with one device

CHILD = r"""
import json, sys, time
sys.path[:0] = sys.argv[1:]
import jax
from bench import common, federation
from conftest import tiny_fed_cell

common.use_compile_cache()
devs = jax.devices()
assert len(devs) == 4, devs
w, conf, job, limits = tiny_fed_cell("fed720m.fused.4chip")
out = {}
for tag, d in (("mesh", devs), ("one", devs[:1])):
    res, checks = federation.run(dict(w, chips=len(d)), conf, job, limits,
                                 2**31 + 77, 1.0, 0, d, time.perf_counter())
    out[tag] = {"correct": res["correct"], "count": res["device"]["count"],
                **{n: v for n, v, _ in checks}}
print(json.dumps(out))
"""


def test_four_device_round_is_sharded_and_matches_one_device():
    root = common.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD, root, os.path.join(root, "src"),
         os.path.dirname(os.path.abspath(__file__))],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    mesh, one = got["mesh"], got["one"]
    assert mesh["correct"] and one["correct"], got
    assert (mesh["count"], mesh["stack_chips"]) == (4, 4)
    assert 1 <= mesh["clients_per_chip"] < 1.5
    assert (one["count"], one["stack_chips"], one["clients_per_chip"]) \
        == (1, 1, 4)
    for k in ("grad_gap", "change_gap"):
        assert abs(mesh[k] - one[k]) <= ENGINE_ATOL, (k, got)
