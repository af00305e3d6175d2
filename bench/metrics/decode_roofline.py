"""Share of its roofline that the whole decode-step program reaches in the
traced window: the least time the chip could take for each step's work
(``work.decode_step``: weights once at code width, the MoE experts the
batch can reach, the live rows' K/V codes, one K/V row written per row,
2 FLOPs per multiply-add), over the device time of the programs launched
under ``repro.decode_step``.  Each step's rows come from the engine's
request records (``context.records``)."""
import work


def read(ctx):
    took, steps = ctx.unique("repro.decode_step", ctx.steps)
    if not steps or not took:
        return None
    least = sum(work.min_time(work.decode_step(ctx.m, lens), ctx.peaks)
                for lens in steps)
    return least / took * 100
