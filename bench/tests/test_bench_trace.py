"""The trace reduction on a hand-built trace with known answers, and on a
small trace recorded on a TPU v5e."""

import glob
import os

import pytest

import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Device ops (µs): A 10–30, B 20–40 (overlaps A), C 60–70; host spans:
# window 0–100, bench.ingest 0–50, bench.broadcast 50–100.
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 5 offset_ps: 60000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%packed_weighted_sum.1 = f32[32,128]{1,0} custom-call(f32[16]{0} %c, u8[16,8,128]{2,1,0} %s)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run(123)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_qat_step(7)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 7 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 8 offset_ps: 50000000 duration_ps: 50000000 } }
  event_metadata { key: 6 value { id: 6 name: "bench.window" } }
  event_metadata { key: 7 value { id: 7 name: "bench.ingest" } }
  event_metadata { key: 8 value { id: 8 name: "bench.broadcast" } }
}
'''


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace.reduce(trace.planes_of(ProfileData.from_text_proto(XSPACE)))


def test_busy_union_and_idle_share(summary):
    assert summary.window_s == pytest.approx(100e-6)
    assert summary.busy_s == pytest.approx(40e-6)        # 10–40 and 60–70
    assert summary.idle_share == pytest.approx(0.6)


def test_time_per_op_and_program(summary):
    assert summary.op_seconds(r"^%fusion")[0] == pytest.approx(30e-6)
    assert summary.op_seconds(r"^%fusion")[1] == 2
    assert summary.op_seconds(r"^%packed_weighted_sum")[0] == pytest.approx(20e-6)
    ops = dict(summary.breakdown()["device_ops"])
    assert ops == {"fusion": pytest.approx(30e-6), "packed_weighted_sum": pytest.approx(20e-6)}


def test_programs_found_by_kernel_and_by_count(summary):
    # the program that holds the fan-in kernel (10–40: the kernel and the
    # fusion before it), and the programs that ran once each
    assert summary.programs_with_op(r"^%?packed_weighted_sum(\.\d+)? ") == (
        pytest.approx(30e-6), 1)
    assert summary.programs_run(1) == (pytest.approx(40e-6), 2)
    assert summary.programs_run(2) == (0.0, 0)


def test_programs_found_whatever_the_program_names_them():
    from jax.profiler import ProfileData

    renamed = XSPACE.replace("jit_run(123)", "jit_fanin_v2(9)").replace(
        "jit_qat_step(7)", "jit_train(8)")
    s = trace.reduce(trace.planes_of(ProfileData.from_text_proto(renamed)))
    assert s.programs_with_op(r"^%?packed_weighted_sum(\.\d+)? ") == (pytest.approx(30e-6), 1)
    assert s.programs_run(1) == (pytest.approx(40e-6), 2)


def test_gap_attribution(summary):
    # gaps 0–10 and 40–50 inside ingest, 50–60 and 70–100 inside broadcast;
    # the gap 40–60 is split at the span edge at 50
    assert summary.gaps_by_span["bench.ingest"] == pytest.approx(20e-6)
    assert summary.gaps_by_span["bench.broadcast"] == pytest.approx(40e-6)
    assert summary.gaps[0] == (pytest.approx(30e-6), "bench.broadcast")
    assert sorted(g for g, _ in summary.gaps) == pytest.approx([10e-6, 20e-6, 30e-6])


def test_short_names():
    assert trace.short_name("%ternary_matmul.39 = f32[8,8192]{1,0} custom-call()") == "ternary_matmul"
    assert trace.short_name("%copy = f32[8]{0} copy()") == "copy"


def test_recorded_chip_trace():
    """A trace of a jitted matmul loop and a fused encode kernel, recorded
    on one TPU v5e inside a ``bench.window`` span."""
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    s = trace.reduce(trace.load(paths[0]))
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0.0 <= s.idle_share < 1.0
    assert s.op_seconds(r"^%quantize_pack_segments")[1] > 0
    t, n = s.programs_with_op(r"^%?quantize_pack_segments(\.\d+)? ")
    assert n > 0 and t >= s.op_seconds(r"^%quantize_pack_segments")[0]
    assert sum(s.gaps_by_span.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    # five 2 ms sleeps between the launches: idle, and inside their span
    assert s.gaps_by_span["bench.sleep"] >= 5 * 0.002
