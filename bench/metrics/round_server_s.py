"""The server's share of a round: ``repro.round.receive`` (decode of the
broadcast), ``repro.round.aggregate`` (ingest and fan-in) and
``repro.broadcast`` inside ``repro.round``, per round, on the host clock."""

import spans


def read(ctx, summary, res):
    w = spans.window(ctx)
    t = w and w.total("repro.round.receive", "repro.round.aggregate", "repro.broadcast",
                      under="repro.round")
    return None if t is None else t / ctx.counters["rounds"]
