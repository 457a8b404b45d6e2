"""The decode window's share of the chip's roofline: for every generate
call, the prefill's least time (its FLOPs, or the weights and the K/V it
writes) and each decode step's (the 2-bit weights, the f32 head and the K/V
of the context read), summed over the calls, over the window."""

import counts


def read(ctx, summary, res):
    f, c = ctx.facts, ctx.config
    pk = counts.peaks(ctx.device_kind)
    b, p = f["batch"], f["prompt"]
    t = counts.roof_seconds(counts.lm_step_flops(c, b * p, (p + 1) / 2),
                            counts.lm_prefill_bytes(c, b, p), pk)
    for j in range(1, f["gen"]):
        t += counts.roof_seconds(counts.lm_step_flops(c, b, p + j),
                                 counts.lm_decode_step_bytes(c, b, p + j), pk)
    return 100.0 * t * f["calls"] / ctx.window_s
