"""The Aggregator's own Python per fold: the self time of ``repro.agg.add``,
``repro.agg.flush`` and ``repro.agg.finalize`` (record checks, per-segment
views and coefficients, the loop over groups, the tree assembly), on the
host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.self_time("repro.agg.add", "repro.agg.flush", "repro.agg.finalize")
    return None if t is None else t / ctx.facts["folds"]
