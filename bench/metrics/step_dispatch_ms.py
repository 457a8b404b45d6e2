"""Dispatch of one decode step: ``repro.serve.step`` (the call of the
jitted step and its argmax, which return once queued unless the device is
behind) over the program's count ``serve.steps``, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.serve.step")
    n = w and w.counters.get("serve.steps")
    return None if t is None or not n else 1e3 * t / n
