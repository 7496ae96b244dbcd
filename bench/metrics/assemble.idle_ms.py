"""Device-idle milliseconds per traced round while the innermost host span
open was the round's ``fed.begin`` (round draws, ClientStore gather),
``fed.assemble`` (batch stacks) or ``fed.scatter`` (ClientStore scatter,
traffic count) (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_round(
        ctx, ("fed.begin", "fed.assemble", "fed.scatter"))
