"""Compile time per call inside the window: the program's counter ``jit.s``,
every tracing, lowering, compile or compile-cache load JAX reported while
the profiler ran (``jit.s/<span>`` names the step), over the calls."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    return None if w is None else w.counters.get("jit.s", 0.0) / ctx.facts["calls"]
