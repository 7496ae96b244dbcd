"""The harness's data-driven parts, the trace reduction, the counts of
operations and bytes, and the entry point without an accelerator."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import common, flops, generator, peaks
from bench import trace as trace_lib

ROOT = common.ROOT


def test_manifest_names_files_that_exist():
    man = common.manifest()
    for c in man["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in man["workloads"]:
        _, conf, traffic, limits = common.cell(w["name"])
        assert conf["name"] == w["config"]
        assert limits
    for pm in man["per_layer"]:
        assert callable(common.metric_reader(pm["name"]))


def test_new_cell_is_found_from_new_files_alone(tmp_path, monkeypatch):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus manifest entries, with no existing file edited."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = common.manifest()
    conf = json.load(open(os.path.join(ROOT, man["configs"][0]["file"])))
    conf["name"] = "toy-config"
    (root / "bench/configs/toy-config.json").write_text(json.dumps(conf))
    (root / "bench/traffic/toy_mix.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 1.0,
         "prompt_len": {"dist": "fixed", "value": 8},
         "output_len": {"dist": "fixed", "value": 1}}))
    (root / "bench/limits/toy.cell.json").write_text('{"logit_gap": 0.1}')
    (root / "bench/metrics/toy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    man["configs"].append({"name": "toy-config", "source": "x",
                           "file": "bench/configs/toy-config.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "toy.cell", "config": "toy-config",
                             "traffic": "toy_mix", "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "toy.metric", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "device", "moves": "setup_s",
                             "workloads": ["toy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(common, "ROOT", str(root))
    monkeypatch.setattr(common, "BENCH_DIR", str(root / "bench"))
    w, c, t, lim = common.cell("toy.cell")
    assert c["name"] == "toy-config" and t["rate_per_s"] == 1.0
    assert lim == {"logit_gap": 0.1}
    assert common.metric_reader("toy.metric")({}) == 42.0


def test_generator_is_deterministic_and_deals_the_same_sizes():
    _, conf, traffic, _ = common.cell("serve720m.chat")
    m = conf["model"]
    args = (traffic, 2**31 + 5, 30.0, m["vocab_size"], m["n_modalities"],
            m["modality_dim"])
    a, b = generator.requests(*args), generator.requests(*args)
    assert np.array_equal(a["due"], b["due"])
    assert all(np.array_equal(x, y) for x, y in zip(a["tokens"], b["tokens"]))
    assert np.array_equal(a["feats"], b["feats"])
    c = generator.requests(traffic, 7, 30.0, m["vocab_size"],
                           m["n_modalities"], m["modality_dim"])
    # another seed: the same schedule of sizes and arrivals, other content
    assert np.array_equal(a["prompt_len"], c["prompt_len"])
    assert np.array_equal(a["max_new"], c["max_new"])
    assert np.array_equal(a["due"], c["due"])
    assert not np.array_equal(a["tokens"][0], c["tokens"][0])
    assert not np.array_equal(a["feats"], c["feats"])
    lo, hi = generator.length_range(traffic["prompt_len"])
    assert a["prompt_len"].min() >= lo and a["prompt_len"].max() <= hi


def _ev(name, a_us, b_us):
    return (name, int(a_us * 1000), int(b_us * 1000))


KERNEL = ('%custom-call.7 = bf16[32,20,64] custom-call(...), '
          'custom_call_target="tpu_custom_call", kernel_name="paged_kernel"')


def test_trace_reduction_busy_programs_and_gaps():
    """A small recorded trace: one device, two programs, a loop op that
    holds other ops, a kernel, and host spans over the idle gaps."""
    planes = [
        ("/device:TPU:0", {
            "XLA Modules": [_ev("jit_step(7)", 0, 400),
                            _ev("jit_step(7)", 1000, 1400),
                            _ev("jit_prefill_paged_fn(3)", 1500, 1900)],
            "XLA Ops": [_ev("%while.3 = (s32[]) while(...)", 0, 400),
                        _ev("%fusion.1 = f32[8] fusion(...)", 0, 100),
                        _ev(KERNEL, 100, 400),
                        _ev("%fusion.1 = f32[8] fusion(...)", 1000, 1100),
                        _ev(KERNEL, 1100, 1400),
                        _ev("%convolution.2 = f32[8] conv(...)", 1500,
                            1900)]}),
        ("/host:CPU", {"python": [_ev("tick", 380, 1450),
                                  _ev("submit", 1400, 1500)]}),
    ]
    s = trace_lib.reduce(planes, window_s=2e-3)
    assert s["busy_s"] == pytest.approx(1.2e-3)
    assert s["programs"]["jit_step"] == (pytest.approx(8e-4), 2)
    assert trace_lib.time_of(s, "paged", "labels") == (pytest.approx(6e-4), 2)
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["tick", pytest.approx(6e-4)]
    assert gaps[1] == ["submit", pytest.approx(1e-4)]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["custom-call:paged_kernel"] == pytest.approx(6e-4)
    assert ops["fusion"] == pytest.approx(2e-4)
    assert "while" not in ops          # a loop's time is its body's


def _four_chips(ops):
    """The same XLA Ops line on four devices."""
    return [(f"/device:TPU:{i}", {"XLA Ops": list(ops)}) for i in range(4)]


def test_device_time_readers_read_per_chip_on_four_devices():
    ops = [_ev("%fusion.1 = f32[8] fusion(...)", 0, 300), _ev(KERNEL, 300,
                                                              400)]
    one = trace_lib.reduce([("/device:TPU:0", {"XLA Ops": ops})], 1e-3)
    four = trace_lib.reduce(_four_chips(ops), 1e-3)
    assert (one["devices"], four["devices"]) == (1, 4)
    assert trace_lib.time_of(one, "paged", "labels") == \
        trace_lib.time_of(four, "paged", "labels") == (pytest.approx(1e-4), 1)
    assert four["busy_s"] == one["busy_s"] == pytest.approx(4e-4)
    # round.mfu over the peak of all the cell's chips
    read = common.metric_reader("round.mfu")
    ctx = {"trace": {"window_s": 2.0}, "rounds_traced": 3,
           "round_flops": 70e12, "device_kind": "TPU v5 lite"}
    mfu1 = read(dict(ctx, chips=1))
    assert mfu1 == pytest.approx(100 * 3 * 70e12 / 2.0 / 197e12)
    assert read(dict(ctx, chips=4)) == pytest.approx(mfu1 / 4)


def test_collective_reader_per_traced_round_per_chip():
    read = common.metric_reader("round.collective_ms")
    ops = [_ev("%fusion.1 = f32[8] fusion(...)", 0, 300),
           _ev("%all-reduce.3 = f32[8] all-reduce(...)", 300, 340),
           _ev("%all-gather-start.1 = (f32[8]) all-gather-start(...)", 340,
               345),
           _ev("%all-gather-done.1 = f32[32] all-gather-done(...)", 345,
               360),
           _ev("%collective-permute.2 = f32[8] collective-permute(...)", 360,
               380),
           _ev("%reduce.4 = f32[] reduce(...)", 380, 400)]
    ctx = {"trace": trace_lib.reduce(_four_chips(ops), 1e-3),
           "rounds_traced": 2}
    assert read(ctx) == pytest.approx(80e-3 / 2)       # 80 us a chip
    ctx["trace"] = trace_lib.reduce(_four_chips(ops[:1] + ops[-1:]), 1e-3)
    assert read(ctx) is None                           # none on the path
    assert read({"trace": None, "rounds_traced": 2}) is None


def test_flops_against_hand_counts_at_published_widths():
    _, conf, job, _ = common.cell("fed720m.fused")
    slm = conf["clients"]["model"]
    d, L, f, V = 1280, 36, 5120, 50257
    layer = 4 * d * d + 2 * d * f            # q, k, v, o and GeLU MLP
    lora = 8 * (d + d) * 4                   # rank 8 on q, k, v, o
    S = 136
    fwd = (2 * 8 * S * L * (layer + lora) + 4 * 8 * S * (S + 1) // 2
           * 20 * 64 * L + 2 * 8 * S * d * V)
    attn = 4 * 8 * S * (S + 1) // 2 * 20 * 64 * L
    step = fwd + (fwd + attn) + 2 * 8 * S * L * lora
    assert flops.train_flops(slm, 8, S) == step
    # the paper SLM's training step: about 3.1 GFLOP a token
    assert 2.9e9 < step / (8 * S) < 3.3e9
    # prefill of 512 prompt tokens and 8 soft tokens, logits at the last
    n = 520
    assert flops.prefill_flops(slm, n) == (
        2 * n * L * layer + 4 * n * (n + 1) // 2 * 20 * 64 * L + 2 * d * V)
    # live pages: 100 and 17 entries in pages of 16 -> 7 + 2 pages
    kv = (7 + 2) * 16 * 20 * 64 * 2 * 2 + 2 * 20 * 64 * 2 * 2
    assert flops.live_kv_bytes(slm, [100, 17], 16) == kv * L
    # a round: 2 clients x 4 steps of the SLM, and 2 SE-CCL steps of the
    # LLM and the server SLM, each with (136) and without (128) the prompt
    llm = conf["server_llm"]
    se = 2 * (flops.train_flops(llm, 8, 136) + flops.train_flops(llm, 8, 128)
              + flops.train_flops(slm, 8, 136) + flops.train_flops(slm, 8, 128))
    assert flops.round_flops(conf, job) == 8 * step + se
    assert 65e12 < 8 * step + se < 75e12


def test_ttft_reader_takes_the_tail_of_every_request_due():
    read = common.metric_reader("admission.ttft_p95_ms")
    ttft = [0.1 * k for k in range(1, 21)]          # 0.1 .. 2.0 s
    assert read({"window_metrics": {"ttft": ttft}}) == pytest.approx(
        1000 * common.quantile(ttft, 0.95))
    # requests never admitted, in the tail: the run reports them as
    # failed, and the metric is left out
    assert read({"window_metrics": {"ttft": ttft + [float("inf")] * 3}}) \
        is None
    assert read({"window_metrics": {"ttft": []}}) is None
    assert read({}) is None


def test_served_tokens_reader_takes_all_the_window():
    read = common.metric_reader("served.tokens_per_s")
    assert read({"window_metrics": {"tokens": 9500}, "window": 50.0}) == 190.0
    assert read({"window_metrics": {"tokens": 0}, "window": 50.0}) is None
    assert read({}) is None


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve720m.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(out):
    return [ln for ln in out.splitlines() if ln.strip().startswith("{")]


def test_run_without_a_tpu_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
