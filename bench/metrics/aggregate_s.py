"""Server ingest per fold: the harness's span from the first
``Aggregator.add`` to the end of ``finalize`` (blocked on the result), on
the host clock."""


def read(ctx, summary, res):
    t = ctx.spans.get("bench.ingest")
    return sum(t) / len(t) if t else None
