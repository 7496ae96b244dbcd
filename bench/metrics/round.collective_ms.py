"""Device milliseconds per traced round, per chip, of the collective
operations (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, and their start and done halves), read from the operation
labels of the profiler trace.  None where the slice holds none, as on one
chip."""
from bench import trace

COLLECTIVES = r"all-reduce|all-gather|reduce-scatter|collective-permute|" \
    r"all-to-all"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds_traced"]:
        return None
    sec, calls = trace.time_of(t, COLLECTIVES, "labels")
    if not calls or sec <= 0:
        return None
    return sec / ctx["rounds_traced"] * 1e3
