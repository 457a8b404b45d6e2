"""Run one benchmark cell once, on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (inputs and weights from the seed, compilation, warm-up) is timed
from process start as ``setup_s``; then the cell's driver measures for
``--seconds`` with the profiler off (``--trace 0``: the cell's end-to-end
metrics) or on (``--trace 1``: its per-layer metrics, read from the trace
and the harness's spans). After the window the driver compares what the
timed path produced with the plain reference. The last line of standard
output is one JSON object; each number compared is printed beside its limit
as the last lines of standard error and as the last key of that object.
Without an accelerator, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def find_device(chips: int) -> dict:
    """The accelerator JAX found, or SystemExit(2): a CPU or too few chips
    gives no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        harness.log(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
                    f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def configure_cache() -> None:
    """The program's persistent compile cache (a fixed directory in the
    checkout, or $JAX_COMPILATION_CACHE_DIR), holding every program."""
    import jax
    from repro.launch.env import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    reg = harness.Registry()
    w = reg.workload(args.workload)
    config = reg.config(w["config"])
    traffic = reg.traffic(w["traffic"])
    driver = reg.driver(traffic)
    model = reg.model(config)
    device = find_device(w["chips"])
    configure_cache()
    ctx = harness.Ctx(cell=w["name"], config=config, traffic=traffic, model=model,
                      seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START, chips=w["chips"], device_kind=device["kind"])
    res = driver.run(ctx)
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    metrics = {}
    out = {"correct": all(c["value"] <= c["limit"] for c in res["compared"].values()),
           "attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        import trace

        try:
            summary = trace.reduce(trace.load(trace.find_xplane(ctx.trace_dir)))
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in reg.per_layer(w["name"]):
            v = reg.reader(m["name"]).read(ctx, summary, res)
            if v is None:
                # left out of the line, as a kernel taken off the path leaves
                # its roofline; said here, so that it never goes unseen
                harness.log(f"bench: per-layer metric {m['name']} found nothing to read "
                            f"in {w['name']}")
                continue
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = summary.breakdown()
    else:
        e2e = dict(res["end_to_end"], setup_s=ctx.setup_s)
        for m in reg.end_to_end(w["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["compared"] = res["compared"]
    for name, c in res["compared"].items():
        harness.log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
