"""Dense fallback per fold: the self time of ``repro.agg.dense``, the
decode and f32 accumulate of every non-ternary upload leaf in
``Aggregator._add_fallback``, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.self_time("repro.agg.dense")
    return None if t is None else t / ctx.facts["folds"]
