"""Every traffic driver runs a whole cell at a tiny size on the CPU (the
device check skipped) and its output agrees with the plain reference."""

import pytest

import tiny


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_driver_runs_and_is_correct(cell):
    ctx = tiny.ctx(cell)
    res = tiny.driver(cell).run(ctx)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert tiny.correct(res), res["compared"]
    assert all(v > 0 for v in res["end_to_end"].values())
    assert ctx.setup_s > 0 and ctx.window_s >= ctx.seconds
    assert ctx.memory_peak_bytes is None or ctx.memory_peak_bytes > 0
