"""Device-idle milliseconds per traced round while the innermost host span
open was the round's ``fed.deliver`` (the downlink channel and the eager
redistribution into the client stacks) (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_round(ctx, ("fed.deliver",))
