"""Mean live slots per decode step in the window, as a share of the
engine's slots, from the engine's own ``slots_active`` histogram (read as
its count and sum at the window's open and close; the engine observes a
step's live slots after the step's finished requests have left)."""


def read(ctx):
    steps, live = ctx.win.occupancy or (0, 0.0)
    return live / steps / ctx.mix["max_slots"] * 100 if steps else None
