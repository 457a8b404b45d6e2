"""Fan-in launches per fold: ``repro.agg.launch``, from each fan-in call to
the block that follows it (dispatch, the rest of the transfer, the kernel),
on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.agg.launch")
    return None if t is None else t / ctx.facts["folds"]
