"""Benchmark harness — one section per paper table/figure. Prints
``name,us_per_call,derived`` CSV (derived = accuracy / ratio / bytes as
appropriate per row; see each bench's docstring).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table4,codec
    PYTHONPATH=src python -m benchmarks.run --only aggregate --smoke   # CI
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.env import configure_compile_cache, pin_runtime

# pinned fast runtime (tcmalloc preload when present, quiet XLA logs) —
# must run before the section modules import jax.
pin_runtime()
configure_compile_cache()

from benchmarks import (  # noqa: E402
    bench_adaptive, bench_aggregate, bench_chaos, bench_encode,
    bench_hierarchy, bench_kernels, bench_robust, bench_serve, bench_tables,
    bench_wire, roofline,
)

SECTIONS = {
    "wire": bench_wire.wire_codec,
    "codecs": bench_wire.codec_table,
    "scenario": bench_wire.scenario_table,
    "aggregate": bench_aggregate.fused_aggregation,
    "encode": bench_encode.fused_encode,
    "hierarchy": bench_hierarchy.fleet_scaling,
    "serve": bench_serve.serve_under_load,
    "chaos": bench_chaos.chaos_sweep,
    "robust": bench_robust.robust_grid,
    "adaptive": bench_adaptive.adaptive_bytes_to_target,
    "kernel_peak": roofline.kernel_peak_table,
    "table2": bench_tables.table2_iid_accuracy,
    "table3": bench_tables.table3_noniid,
    "table4": bench_tables.table4_comm_costs,
    "fig7": bench_tables.fig7_batch_sizes,
    "fig10": bench_tables.fig10_participation,
    "fig11": bench_tables.fig11_unbalanced,
    "sparsity": bench_tables.sparsity_report,
    "codec": bench_kernels.codec_roundtrip,
    "quantizer": bench_kernels.quantizer_cost,
    "gemm_model": bench_kernels.ternary_matmul_hbm_model,
    "xpod_model": bench_kernels.collective_wire_model,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sanity mode: same code paths, minimal repeats")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        import benchmarks.common as common

        common.SMOKE = True

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SECTIONS.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(",".join(str(v) for v in row), flush=True)
        except Exception as e:
            failures += 1
            print(f"{name}_ERROR,0,{type(e).__name__}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# section {name} took {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
