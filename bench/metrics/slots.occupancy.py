"""Share of the decode slots that held a request, over the decode steps of
the traced slice: the busy slots each ``serve.step`` span carries
(``busy``), summed, over the steps times the engine's slots (the same
ratio as the increments of ``ServingEngine.stats()``'s ``slot_steps`` over
``steps`` times ``slots_total``)."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None:
        return None
    busy = [args["busy"] for _, _, args in spans.named(s, "serve.step")
            if "busy" in args]
    if not busy:
        return None
    return 100.0 * sum(busy) / (len(busy) * ctx["conf"]["engine"]["n_slots"])
