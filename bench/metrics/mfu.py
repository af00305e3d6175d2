"""Model FLOPs of the traced window over the window times the chip's peak:
the decode steps' and prefills' FLOPs (``work.py``: 2 per weight per token,
attention over the live context, the causal half for prefill) whose
programs ran inside the window."""
import work


def read(ctx):
    _, steps = ctx.unique("repro.decode_step", ctx.steps)
    _, fills = ctx.unique("repro.prefill", ctx.prefills)
    if not steps and not fills:
        return None
    flops = sum(work.decode_step(ctx.m, lens)["flops"] for lens in steps)
    flops += sum(work.prefill(ctx.m, T)["flops"] for T in fills)
    a, b = ctx.trace_window
    return flops / ((b - a) * ctx.peaks["flops"] * ctx.n_devices) * 100
