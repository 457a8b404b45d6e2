"""Dispatch of one client step: ``repro.client.step`` (the call of the
jitted QAT step in ``local_train``, which returns once the step is queued
unless the device is behind) over ``client.steps``, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.client.step")
    n = w and w.counters.get("client.steps")
    return None if t is None or not n else 1e3 * t / n
