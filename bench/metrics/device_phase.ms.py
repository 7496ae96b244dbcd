"""Device milliseconds per traced round of the operations under the
program's named scope ``device_phase``: the clients' CCL and AMT scans, read
from the op names in the profiler trace (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "device_phase")
