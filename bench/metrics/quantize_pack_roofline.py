"""Re-quantize kernel's share of its roofline: the least time to read the
f32 global's ternary leaves and write their 2-bit codes
(``counts.requantize_cost``), for the folds of the window, over the device
time of the ``quantize_pack_segments`` kernel (``kernels/quantize_pack.py``)."""

import counts


def read(ctx, summary, res):
    t, n = summary.op_seconds(r"^%?quantize_pack_segments(\.\d+)? ")
    if not n:
        return None
    f = ctx.facts
    flops, nbytes = counts.requantize_cost(f["n_ternary"])
    roof = f["folds"] * counts.roof_seconds(flops, nbytes, counts.peaks(ctx.device_kind))
    return 100.0 * roof / t
