"""Device milliseconds per traced round of the operations under the
program's named scope ``server_phase``: the SE-CCL scan on the server LLM
and SLM, read from the op names in the profiler trace
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "server_phase")
