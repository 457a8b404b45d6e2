"""Upload encode per round: ``repro.client.encode`` in ``train_client``
(the client payload, the residual codec and ``encode_update`` of every
client), on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.client.encode")
    return None if t is None else t / ctx.counters["rounds"]
