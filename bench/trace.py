"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time (the union of the intervals in which an operation
ran), the idle share of the window, device time per operation and per
compiled program, and the idle gaps, each attributed to the harness's own
host span that was open across it.

Planes whose name is ``/device:<KIND>:<n>`` are devices; the operations are
the events of their ``XLA Ops`` line and the compiled programs those of
their ``XLA Modules`` line. A program is found by what it does, never by the
name the program gave it: by a kernel that runs inside it
(``programs_with_op``), or by how often it ran against a count the harness
keeps (``programs_run``). Host spans are the events whose name starts with
the harness's prefix (``bench.``), on any line of any host plane. Host and
device events share the trace's clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Summary:
    n_devices: int
    window_s: float
    busy_s: float                 # mean over devices of the busy union
    op_s: dict                    # op name -> device seconds (mean over devices)
    op_n: dict                    # op name -> count (mean over devices)
    module_s: dict                # program name -> device seconds
    module_n: dict
    module_ops: dict              # program name -> names of the ops run inside it
    gaps_by_span: dict            # host span name -> idle seconds inside it
    gaps: list                    # (seconds, span name) of each idle gap, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def op_seconds(self, pattern: str) -> tuple[float, float]:
        """(device seconds, count) of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hits = [k for k in self.op_s if rx.search(k)]
        return sum(self.op_s[k] for k in hits), sum(self.op_n[k] for k in hits)

    def programs_with_op(self, pattern: str) -> tuple[float, float]:
        """(device seconds, runs) of the compiled programs inside which an
        op whose name matches ``pattern`` ran."""
        rx = re.compile(pattern)
        hits = [k for k, ops in self.module_ops.items() if any(rx.search(o) for o in ops)]
        return sum(self.module_s[k] for k in hits), sum(self.module_n[k] for k in hits)

    def programs_run(self, n: float) -> tuple[float, float]:
        """(device seconds, runs) of the compiled programs that ran exactly
        ``n`` times in the window."""
        hits = [k for k, c in self.module_n.items() if round(c) == round(n)]
        return sum(self.module_s[k] for k in hits), sum(self.module_n[k] for k in hits)

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, by short name (an op nested
        in a loop counts inside its loop too), and the idle time inside each
        harness span."""
        by: dict = {}
        for k, v in self.op_s.items():
            by[short_name(k)] = by.get(short_name(k), 0.0) + v
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in sorted(
                self.gaps_by_span.items(), key=lambda kv: -kv[1])[:top]],
        }


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] ...`` → ``fusion``: the HLO instruction's
    name without its text, sigil or number."""
    return re.sub(r"\.\d+$", "", op.split(" = ", 1)[0].lstrip("%"))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def planes_of(profile) -> list[tuple[str, list[tuple[str, list[Event]]]]]:
    """(plane name, [(line name, events)]) of a ``jax.profiler.ProfileData``."""
    return [(p.name, [(ln.name, _events(ln)) for ln in p.lines])
            for p in profile.planes]


def load(path: str):
    from jax.profiler import ProfileData

    return planes_of(ProfileData.from_file(path))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce(planes, window: tuple[float, float] | None = None) -> Summary:
    """Reduce parsed planes over ``window`` (seconds on the trace clock; by
    default the ``bench.window`` host span, else the span of device ops)."""
    spans: list[Event] = []
    devices = []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            d = dict(lines)
            devices.append((d.get(OPS_LINE, []), d.get(MODULES_LINE, [])))
        else:
            for _, evs in lines:
                spans.extend(e for e in evs if e.name.startswith(SPAN_PREFIX))
    if not devices:
        raise ValueError("trace holds no device plane")
    if window is None:
        w = [e for e in spans if e.name == WINDOW_SPAN]
        if w:
            window = (w[0].start, w[0].end)
        else:
            allops = [e for ops, _ in devices for e in ops]
            window = (min(e.start for e in allops), max(e.end for e in allops))
    lo, hi = window
    nd = len(devices)
    busy = 0.0
    op_s: dict = {}
    op_n: dict = {}
    mod_s: dict = {}
    mod_n: dict = {}
    mod_ops: dict = {}
    gap_iv: list[tuple[float, float]] = []
    for ops, mods in devices:
        ivs = _union(_clip([(e.start, e.end) for e in ops], lo, hi))
        busy += sum(e - s for s, e in ivs)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        gap_iv.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i])
        for e in ops:
            if e.end > lo and e.start < hi:
                op_s[e.name] = op_s.get(e.name, 0.0) + (min(e.end, hi) - max(e.start, lo)) / nd
                op_n[e.name] = op_n.get(e.name, 0) + 1 / nd
        for e in mods:
            if e.end > lo and e.start < hi:
                mod_s[e.name] = mod_s.get(e.name, 0.0) + (min(e.end, hi) - max(e.start, lo)) / nd
                mod_n[e.name] = mod_n.get(e.name, 0) + 1 / nd
        # each op to the program whose run holds it (runs on one device do
        # not overlap)
        runs = sorted(mods, key=lambda e: e.start)
        starts = [e.start for e in runs]
        for e in ops:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start <= runs[i].end:
                mod_ops.setdefault(runs[i].name, set()).add(e.name)
    inner = sorted((e for e in spans if e.name != WINDOW_SPAN), key=lambda e: e.start)
    by_span: dict = {}
    gaps = []
    for s, e in gap_iv:
        # split the gap at the edges of the spans inside it, and give each
        # piece to the innermost (shortest) span that covers it
        near = [sp for sp in inner if sp.start < e and sp.end > s]
        cuts = sorted({s, e, *(x for sp in near for x in (sp.start, sp.end) if s < x < e)})
        share: dict = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [sp for sp in near if sp.start <= mid <= sp.end]
            name = min(cover, key=lambda sp: sp.end - sp.start).name if cover else "outside spans"
            share[name] = share.get(name, 0.0) + (b - a) / nd
        for name, t in share.items():
            by_span[name] = by_span.get(name, 0.0) + t
        gaps.append(((e - s) / nd, max(share, key=share.get)))
    gaps.sort(key=lambda g: -g[0])
    return Summary(n_devices=nd, window_s=hi - lo, busy_s=busy / nd, op_s=op_s,
                   op_n=op_n, module_s=mod_s, module_n=mod_n,
                   module_ops=mod_ops, gaps_by_span=by_span, gaps=gaps)
