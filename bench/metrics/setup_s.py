"""Seconds from process start to the opening of the measured window:
weights made and coded, every program compiled or loaded from the cache,
warm-up requests, and the ramp that fills the slots."""


def read(ctx):
    return ctx.setup_s
