"""The program's own spans and counters (``repro.obs``) in a traced run,
for the per-layer readers in ``metrics/``.

The program keeps them on the host clock (``time.perf_counter``, the
clock of ``harness.Ctx``) while the profiler runs, which in a traced run is
the window. ``window(ctx)`` keeps the spans that start inside the window,
``[ctx.t_start + ctx.setup_s, + ctx.window_s]``, and gives each its total
time and its self time: its duration less what its child spans cover. It
returns None where the program keeps no spans (a program without
``repro.obs``), so that a reader leaves its metric out.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    self_s: float
    ancestors: tuple            # names of the spans it lies inside, innermost first

    @property
    def total_s(self) -> float:
        return self.end - self.start


class Window:
    def __init__(self, spans: list[Span], counters: dict):
        self.spans, self.counters = spans, counters

    def named(self, *names: str, under: str | None = None) -> list[Span]:
        """The spans called one of ``names`` (inside a span called
        ``under``, where given)."""
        return [s for s in self.spans
                if s.name in names and (under is None or under in s.ancestors)]

    def total(self, *names: str, under: str | None = None) -> float | None:
        """Summed duration of those spans; None where there is none."""
        got = self.named(*names, under=under)
        return sum(s.total_s for s in got) if got else None

    def self_time(self, *names: str, under: str | None = None) -> float | None:
        """Summed self time of those spans; None where there is none."""
        got = self.named(*names, under=under)
        return sum(s.self_s for s in got) if got else None


def reduce(records: dict, lo: float, hi: float) -> Window:
    """``repro.obs.records()`` cut to the spans that start in ``[lo, hi]``
    and have ended."""
    raw = records["spans"]
    child = [0.0] * len(raw)
    for name, parent, t0, t1, _ in raw:
        if parent >= 0 and t1 is not None:
            child[parent] += t1 - t0
    spans = []
    for i, (name, parent, t0, t1, _) in enumerate(raw):
        if t1 is None or not lo <= t0 <= hi:
            continue
        anc, p = [], parent
        while p >= 0:
            anc.append(raw[p][0])
            p = raw[p][1]
        spans.append(Span(name, t0, t1, (t1 - t0) - child[i], tuple(anc)))
    return Window(spans, dict(records["counters"]))


def window(ctx) -> Window | None:
    try:
        from repro import obs
    except ImportError:
        return None
    lo = ctx.t_start + ctx.setup_s
    return reduce(obs.records(), lo, lo + ctx.window_s)
