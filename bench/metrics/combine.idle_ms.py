"""Device-idle milliseconds per traced round while the innermost host span
open was the round's ``fed.decode`` (uploads decoded at the phase
boundary) or ``fed.combine`` (the eager MMA combine)
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_round(ctx, ("fed.decode", "fed.combine"))
