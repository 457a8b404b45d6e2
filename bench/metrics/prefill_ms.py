"""Prefill per ``generate`` call: ``repro.serve.prefill``, up to the block
on its logits, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.serve.prefill")
    return None if t is None else 1e3 * t / ctx.facts["calls"]
