"""The program tracer ``repro.obs``: off outside a profiler session, nested
spans and counters inside one, compile time charged to the innermost span,
and every span in the profiler's own trace on the same clock as the rest."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


@pytest.fixture
def session(tmp_path):
    """A profiler session around the test body; yields the trace dir."""
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield str(tmp_path)
    finally:
        jax.profiler.stop_trace()
        obs.clear()


def by_name(rec):
    return {s[0]: (i, s) for i, s in enumerate(rec["spans"])}


def test_off_outside_a_session_keeps_nothing():
    obs.clear()
    assert obs.span("a") is obs.span("b", k=1)
    with obs.span("a"):
        with obs.span("b"):
            obs.count("n", 3)
    assert obs.records() == {"spans": [], "counters": {}}


def test_nested_spans_parents_self_time_and_counters(session):
    with obs.span("outer", round=7):
        with obs.span("inner"):
            obs.count("steps")
            np.linalg.qr(np.ones((200, 200)))
        with obs.span("inner2"):
            obs.count("steps", 2)
    rec = obs.records()
    spans = by_name(rec)
    (io, outer), (_, inner), (_, inner2) = spans["outer"], spans["inner"], spans["inner2"]
    assert outer[1] == -1 and inner[1] == io and inner2[1] == io
    assert outer[4] == {"round": 7} and inner[4] is None
    for s in (outer, inner, inner2):
        assert s[3] is not None and s[3] >= s[2]
    assert outer[2] <= inner[2] <= inner[3] <= inner2[2] <= inner2[3] <= outer[3]
    self_s = (outer[3] - outer[2]) - (inner[3] - inner[2]) - (inner2[3] - inner2[2])
    assert 0 <= self_s < outer[3] - outer[2]
    assert rec["counters"]["steps"] == 3


def test_records_past_the_cap_are_counted_not_kept(session, monkeypatch):
    monkeypatch.setattr(obs, "CAP", 2)
    for _ in range(5):
        with obs.span("s"):
            pass
    rec = obs.records()
    assert len(rec["spans"]) == 2 and rec["counters"]["obs.dropped"] == 3


def test_a_rejit_is_charged_to_the_innermost_span(session):
    x = jnp.arange(8.0)
    warm = jax.jit(lambda v: v * 2.0)
    warm(x).block_until_ready()
    with obs.span("outer"):
        with obs.span("warm"):
            warm(x).block_until_ready()          # compiled before: nothing
        with obs.span("fresh"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    c = obs.records()["counters"]
    assert c["jit.s/fresh"] > 0 and "jit.s/warm" not in c
    assert "jit.s/outer" not in c and c["jit.s"] >= c["jit.s/fresh"]


def test_every_span_is_on_a_host_plane_of_the_trace_inside_the_window(tmp_path):
    names = ["repro.test.a", "repro.test.b", "repro.test.c"]
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            with obs.span(names[0], k=1):
                with obs.span(names[1]):
                    jnp.ones(4).block_until_ready()
            with obs.span(names[2]):
                pass
    finally:
        jax.profiler.stop_trace()
        obs.clear()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    (lo, hi), = events["test.window"]
    for n in names:
        (s, e), = events[n]
        assert lo <= s <= e <= hi, n
    (sa, ea), (sb, eb) = events[names[0]][0], events[names[1]][0]
    assert sa <= sb <= eb <= ea


def test_aggregator_spans_nest_as_the_ingest_runs(session):
    """One fold through the program's ``Aggregator``: each upload's decode
    under its add, the staging, transfer and launch under a flush, one
    launch counted per fan-in call, and the dense fallback under its add."""
    from repro.comm.wire import encode_update
    from repro.core import FTTQConfig
    from repro.core.tfedavg import server_requantize
    from repro.fed import Aggregator

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    blob = encode_update(server_requantize(params, FTTQConfig()))
    obs.clear()
    agg = Aggregator(chunk_c=2)
    for w in (1.0, 2.0, 3.0):
        agg.add(blob, weight=w)
    agg.finalize()
    rec = obs.records()
    spans = rec["spans"]
    name = [s[0] for s in spans]
    parent = [name[s[1]] if s[1] >= 0 else None for s in spans]
    assert name.count("repro.agg.add") == 3
    assert {p for n, p in zip(name, parent) if n == "repro.wire.decode"} == {"repro.agg.add"}
    assert {p for n, p in zip(name, parent) if n == "repro.agg.dense"} == {"repro.agg.add"}
    assert {p for n, p in zip(name, parent) if n == "repro.agg.flush"} == {
        "repro.agg.add", "repro.agg.finalize"}
    for n in ("repro.agg.stage", "repro.agg.transfer", "repro.agg.launch"):
        assert {p for m, p in zip(name, parent) if m == n} == {"repro.agg.flush"}
    assert rec["counters"]["agg.launches"] == name.count("repro.agg.launch") == 2
