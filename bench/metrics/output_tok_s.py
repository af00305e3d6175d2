"""Output tokens streamed to clients in the window, per second of window."""


def read(ctx):
    return len(ctx.stamps_in_window()) / (ctx.win.t1 - ctx.win.t0)
