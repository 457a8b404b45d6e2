"""Wire decode per fold: the self time of the program's ``repro.wire.decode``
spans (frame CRC and record parse, ``comm/wire.py``) inside
``Aggregator.add``, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.self_time("repro.wire.decode", under="repro.agg.add")
    return None if t is None else t / ctx.facts["folds"]
