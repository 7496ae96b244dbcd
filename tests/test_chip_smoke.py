"""``chip_smoke.py`` on the CPU: its three phases at ``ModelConfig.reduced()``
widths, called in this process, and the rule that a run without a TPU never
prints the result line; and where the persistent compile cache lives."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def _ok_lines(text):
    out = []
    for line in text.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            out.append(obj)
    return out


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="the CPU rehearsal of the chip script")
def test_reduced_phases_pass_and_print_no_result(capsys):
    assert chip_smoke.main([]) != 0           # no TPU: nothing runs
    assert chip_smoke.main(["--reduced"]) == 0
    out = capsys.readouterr().out
    phases = [line.split("phase=")[1].split()[0]
              for line in out.splitlines() if line.startswith("smoke-timing")]
    assert phases == ["fused_round", "split_int8", "serving"]
    assert "finite=True" in out and "greedy_tokens_equal=True" in out
    assert "requests=8/8" in out
    assert _ok_lines(out) == [] and '"ok"' not in out


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """``use_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``; a compile in a fresh process writes its
    entry to that directory, and JAX's own setting names no other."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    target = (str(tmp_path / "cache") if from_env
              else os.path.join(checkout, ".jax_cache"))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(checkout, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = target
    # a program no other process compiles, so its cache entry is new
    factor = f"{int(time.time() * 1e6) % 10 ** 9}.5"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.mesh import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            f"jax.jit(lambda x: x * {factor} + 1)(jnp.ones((3, 5)))"
            ".block_until_ready()\n")
    before = set(os.listdir(target)) if os.path.isdir(target) else set()
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == [target, target]
    assert set(os.listdir(target)) - before
    assert [p.name for p in tmp_path.iterdir()] == (
        ["cache"] if from_env else [])
