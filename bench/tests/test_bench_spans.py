"""The readers of the program's own spans (``spans.py``), on every cell at
a tiny size with the profiler on, as in a ``--trace 1`` run: each gives a
number, and the fold's six ingest parts cover the harness's ingest span."""

import functools
import os
import re
import shutil

import pytest

import harness
import spans
import tiny
from repro import obs

INGEST = ("ingest_decode_s", "ingest_dense_s", "ingest_records_s", "ingest_stage_s",
          "ingest_transfer_s", "ingest_launch_s")
PROGRAM = INGEST + (
    "fanin_launches", "broadcast_requantize_s", "broadcast_serialize_s", "client_init_s",
    "client_transfer_ms", "client_dispatch_ms", "client_encode_s", "round_server_s",
    "prefill_ms", "step_dispatch_ms", "jit_s.round", "jit_s.fold", "jit_s.decode")


def test_every_reader_of_the_program_spans_is_listed():
    d = os.path.join(harness.BENCH, "metrics")
    readers = {f[:-3] for f in os.listdir(d)
               if f.endswith(".py") and re.search(r"^import spans$",
                                                  open(os.path.join(d, f)).read(), re.M)}
    assert readers == set(PROGRAM)


@functools.lru_cache(maxsize=None)
def traced(cell: str) -> dict:
    """The readings of one run of ``cell`` with the profiler on over its
    window, its process's first as in ``run.py`` (the program's records
    start empty), taken before another run clears them."""
    obs.clear()
    ctx = tiny.ctx(cell)
    ctx.trace = True
    try:
        res = tiny.driver(cell).run(ctx)
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    reg = harness.Registry()
    return {m["name"]: reg.reader(m["name"]).read(ctx, None, res) for m in reg.per_layer(cell)
            if m["name"] in PROGRAM or m["name"] == "aggregate_s"}


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_each_reader_gives_a_number_in_its_cells(cell):
    got = {k: v for k, v in traced(cell).items() if k in PROGRAM}
    assert got
    for name, v in got.items():
        assert v is not None and v >= 0, (cell, name, v)


@pytest.mark.parametrize("cell", sorted(c for c in tiny.CELLS if c.startswith("fold.")))
def test_the_ingest_parts_cover_the_ingest_span(cell):
    got = traced(cell)
    parts = sum(got[n] for n in INGEST)
    assert 0.8 * got["aggregate_s"] <= parts <= 1.05 * got["aggregate_s"], got


def test_window_self_time_and_ancestors():
    rec = {"spans": [("a", -1, 0.0, 10.0, None), ("b", 0, 1.0, 4.0, None),
                     ("c", 1, 2.0, 3.0, None), ("b", 0, 5.0, 6.0, None),
                     ("d", -1, 11.0, 12.0, None), ("e", -1, 0.5, None, None)],
            "counters": {"n": 2}}
    w = spans.reduce(rec, 0.0, 10.5)
    assert [s.name for s in w.spans] == ["a", "b", "c", "b"]
    assert w.self_time("a") == 6.0 and w.self_time("b") == 3.0
    assert w.total("b") == 4.0 and w.total("c", under="a") == 1.0
    assert w.total("b", under="c") is None and w.counters == {"n": 2}
