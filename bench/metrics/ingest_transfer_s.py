"""Host-to-device issue per fold: ``repro.agg.transfer``, the put of each
staging buffer and its coefficients to the device, on the host clock (the
part of the copy that outlasts it lands in ``ingest_launch_s``)."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.agg.transfer")
    return None if t is None else t / ctx.facts["folds"]
