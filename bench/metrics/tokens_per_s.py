"""Output tokens of the window's whole steps over their time: the tokens
emitted after the last step that returned by the opening, up to the last
that returned before the close, over the time between those returns
(``loop.window_stats``)."""


def read(ctx):
    return ctx.stats["tokens_per_s"] or None
