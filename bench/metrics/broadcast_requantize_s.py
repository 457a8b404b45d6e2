"""Re-quantize per fold: ``repro.broadcast.requantize`` inside
``broadcast_blob`` (``server_requantize`` and the residual codec on the raw
leaves), on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.broadcast.requantize")
    return None if t is None else t / ctx.facts["folds"]
