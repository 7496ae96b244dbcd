"""Mean host milliseconds of an admission in the traced slice: the
program's ``serve.admit`` spans, each from the admission's start
(``Request.t_admit``) to its first token in place (``Request.t_first``):
the prefill, the host's read of the first token, and the insert."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None:
        return None
    d = [b - a for a, b, _ in spans.named(s, "serve.admit")]
    return sum(d) / len(d) * 1e-6 if d else None
