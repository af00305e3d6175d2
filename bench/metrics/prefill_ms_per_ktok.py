"""Device time of the prefill programs in the traced window (launched
under ``repro.prefill``) per 1,000 prompt tokens of the admissions that
launched them."""


def read(ctx):
    took, fills = ctx.unique("repro.prefill", ctx.prefills)
    toks = sum(fills)
    if not toks:
        return None
    return took / (toks / 1e3) * 1e3
