"""Mean device time of one decode-step program run in the traced window
(programs launched under the engine's ``repro.decode_step`` annotation)."""


def read(ctx):
    runs = ctx.program_runs("repro.decode_step")
    if not runs:
        return None
    return sum(x.end - x.start for x in runs) / len(runs) * 1e3
