"""Broadcast serialize per fold: ``repro.wire.encode`` inside
``repro.broadcast`` (the copy of device leaves to the host, the framing,
the CRC), on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.wire.encode", under="repro.broadcast")
    return None if t is None else t / ctx.facts["folds"]
