"""Shared helpers of the benchmark's CPU tests: the harness package on the
path, and the committed cells cut to toy widths."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _tiny(m, d=64, kv=4, vocab=500, connector=48):
    m = dict(m)
    m.update(n_layers=2, d_model=d, n_heads=4, n_kv_heads=kv, head_dim=16,
             d_ff=128, vocab_size=vocab, modality_dim=16)
    if m.get("connector_dim"):
        m["connector_dim"] = connector
    return m


@pytest.fixture
def tiny_serve():
    """(workload, configuration, traffic, limits) of ``serve720m.chat`` at
    toy widths and a toy load."""
    from bench import common
    w, conf, traffic, limits = common.cell("serve720m.chat")
    conf = copy.deepcopy(conf)
    conf["model"] = _tiny(conf["model"])
    conf["engine"].update(n_slots=4, n_pages=64, max_pages_per_seq=8,
                          max_out=16, buckets=[16, 32, 64], use_kernel=False)
    traffic = dict(traffic, rate_per_s=20.0,
                   prompt_len={"dist": "lognormal", "median": 20,
                               "sigma": 0.7, "min": 4, "max": 60},
                   output_len={"dist": "lognormal", "median": 6,
                               "sigma": 0.5, "min": 2, "max": 16})
    return w, conf, traffic, dict(limits, checked_tokens=10)


def tiny_fed_cell(name: str = "fed720m.fused") -> tuple:
    """(workload, configuration, job, limits) of the federation cell
    ``name`` at toy widths: a toy SLM pair and a toy server LLM of another
    width."""
    from bench import common
    w, conf, job, limits = common.cell(name)
    conf = copy.deepcopy(conf)
    conf["clients"]["model"] = _tiny(conf["clients"]["model"])
    conf["clients"]["model"]["connector_dim"] = 48
    conf["server_llm"] = _tiny(conf["server_llm"], d=96, kv=2, vocab=700)
    job = dict(job, seq_len=24, template_len=4, samples=400)
    return w, conf, job, limits


@pytest.fixture
def tiny_fed():
    """:func:`tiny_fed_cell` of ``fed720m.fused``."""
    return tiny_fed_cell()
