"""Broadcast per fold: the harness's span around ``broadcast_blob`` (the
re-quantize of the new global and its serialization), on the host clock."""


def read(ctx, summary, res):
    t = ctx.spans.get("bench.broadcast")
    return sum(t) / len(t) if t else None
