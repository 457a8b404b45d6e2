"""Host-to-device issue of one client batch: ``repro.client.transfer``
(the two ``jnp.asarray`` in ``local_train``) over the program's count of
client steps ``client.steps``, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.client.transfer")
    n = w and w.counters.get("client.steps")
    return None if t is None or not n else 1e3 * t / n
