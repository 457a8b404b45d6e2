"""The whole fold's share of the chip's roofline: the least time for the
fold's algorithmic work (every upload's codes and raw leaves in, the f32
global out, the re-quantize; ``counts.fold_cost``) over the time a fold took
in the window. The fold is bound by memory, so this is a share of the
bandwidth roof, named ``mfu`` as the whole step's share."""

import counts


def read(ctx, summary, res):
    f = ctx.facts
    flops, nbytes = counts.fold_cost(f["uploads_per_fold"], f["n_ternary"], f["n_raw"])
    roof = counts.roof_seconds(flops, nbytes, counts.peaks(ctx.device_kind))
    return 100.0 * roof * f["folds"] / ctx.window_s
