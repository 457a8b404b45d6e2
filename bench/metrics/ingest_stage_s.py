"""Staging per fold: ``repro.agg.stage``, the copies of each group's upload
bytes (padding included) into the host staging buffer, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.agg.stage")
    return None if t is None else t / ctx.facts["folds"]
