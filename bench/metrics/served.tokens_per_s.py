"""Output tokens whose tick ended in the window, over the whole window, on
the host clock.  Below capacity this is the offered load less the tokens
still owed at the window's close, so it moves with the decode step's time
only by that backlog."""


def read(ctx):
    tokens = (ctx.get("window_metrics") or {}).get("tokens")
    if not tokens or not ctx.get("window"):
        return None
    return tokens / ctx["window"]
