"""Device milliseconds per call of the serving engine's jitted decode step
(``jit_step`` in the trace)."""
from bench import trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    s, n = trace.time_of(ctx["trace"], r"^jit_step$")
    return s / n * 1e3 if n else None
