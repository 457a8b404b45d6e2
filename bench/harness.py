"""The benchmark's registry and run context.

Everything is found by name. ``BENCHMARK.json`` at the checkout root lists
the cells; a cell names a configuration (``configs/<name>.json``, whose
``family`` names the model module ``models/<family>.py`` that holds the
plain reference) and a traffic mix (``traffic/<name>.json``, whose
``driver`` names ``drivers/<driver>.py``). Each per-layer metric is read by
``metrics/<metric name>.py``. Adding a cell, a configuration, a traffic mix
or a metric therefore adds files and manifest entries and edits none.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def limits(cell: str) -> dict:
    """The limits of the numbers a cell compares (``limits/<cell>.json``),
    each set from a program's and a control's readings (see PERF.md)."""
    return load_json(os.path.join(BENCH, "limits", f"{cell}.json"))


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """Resolves names to files under ``bench`` (or another root, for tests)."""

    def __init__(self, root: str | None = None, bench: str | None = None):
        self.root, self.bench = root or ROOT, bench or BENCH
        self.manifest = load_json(os.path.join(self.root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def driver(self, traffic: dict):
        d = traffic["driver"]
        return load_module(os.path.join(self.bench, "drivers", f"{d}.py"), f"bench_driver_{d}")

    def model(self, config: dict):
        f = config["family"]
        return load_module(os.path.join(self.bench, "models", f"{f}.py"), f"bench_model_{f}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench, "metrics", f"{metric}.py"),
                           "bench_metric_" + metric.replace(".", "_"))

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.manifest["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.manifest["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in e2e else [])]


class Ctx:
    """What a driver sees: its cell, the seed, the window's clock, harness
    spans (host clock, and ``jax.profiler.TraceAnnotation`` in a traced
    run) and counters, and a place for the numbers it compares."""

    def __init__(self, *, cell: str, config: dict, traffic: dict, model, seed: int,
                 seconds: float, trace: bool, t_start: float, chips: int = 1,
                 device_kind: str = ""):
        self.cell, self.config, self.traffic, self.model = cell, config, traffic, model
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start, self.chips, self.device_kind = t_start, chips, device_kind
        self.spans: dict[str, list[float]] = {}
        self.counters: dict[str, float] = {}
        self.setup_s = None
        self.window_s = None
        self.trace_dir = None
        self.memory_peak_bytes = None
        self.facts: dict = {}
        self._t0 = None
        self._window_ann = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def begin_window(self) -> None:
        import jax

        self.spans.clear()
        self.counters.clear()
        if self.trace:
            import tempfile

            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_ann = jax.profiler.TraceAnnotation("bench.window")
            self._window_ann.__enter__()
        self._t0 = time.perf_counter()
        self.setup_s = self._t0 - self.t_start

    def window_over(self) -> bool:
        return time.perf_counter() - self._t0 >= self.seconds

    def end_window(self) -> None:
        import jax

        self.window_s = time.perf_counter() - self._t0
        if self.trace:
            self._window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def closed_loop(self, unit, *, warmup: int, check: int, check_within: int, rng,
                    counter: str, keep=lambda out: out) -> dict:
        """Run ``warmup`` units (numbered -1, -2, ...), open the window, run
        units 0, 1, ... back to back until it has closed, and count them
        under ``counter``. Returns ``keep`` of the answers of ``check``
        units drawn by ``rng`` from the first ``check_within``, or of the
        last unit where the window closed before any of those."""
        for u in range(warmup):
            unit(-1 - u)
        want = {int(i) for i in rng.choice(check_within, check, replace=False)}
        kept = {}
        self.begin_window()
        u = 0
        while True:
            out = unit(u)
            if u in want:
                kept[u] = keep(out)
            u += 1
            if self.window_over():
                break
        self.end_window()
        if not kept:
            kept[u - 1] = keep(out)
        self.count(counter, u)
        return kept

    def read_memory(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[: self.chips]]
        self.memory_peak_bytes = max((p for p in peaks if p is not None), default=None)


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
