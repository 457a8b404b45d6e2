"""Fan-in's share of its roofline: the least time the chip needs to read
every upload's ternary codes and write the f32 sum (``counts.fanin_cost``),
for the folds of the window, over the device time of the compiled programs
that launch the fan-in kernel ``packed_weighted_sum``
(``kernels/aggregate.py``): the kernel and whatever those programs do
around it, such as the unpack transpose."""

import counts


def read(ctx, summary, res):
    t, n = summary.programs_with_op(r"^%?packed_weighted_sum(\.\d+)? ")
    if not n:
        return None
    f = ctx.facts
    flops, nbytes = counts.fanin_cost(f["uploads_per_fold"], f["n_ternary"])
    roof = f["folds"] * counts.roof_seconds(flops, nbytes, counts.peaks(ctx.device_kind))
    return 100.0 * roof / t
