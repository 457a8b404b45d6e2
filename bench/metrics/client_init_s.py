"""Client set-up per round: ``repro.client.init`` in ``local_train`` (the
optimizer state and the FTTQ ``w_q`` of every client), on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.client.init")
    return None if t is None else t / ctx.counters["rounds"]
