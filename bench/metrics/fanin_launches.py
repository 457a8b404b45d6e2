"""Fan-in launches per fold: the program's counter ``agg.launches``, one
for every staging buffer ``Aggregator._flush_group`` hands to the kernel."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    n = w and w.counters.get("agg.launches")
    return None if n is None else n / ctx.facts["folds"]
