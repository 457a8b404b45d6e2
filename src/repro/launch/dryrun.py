import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell against the production mesh with
512 placeholder host devices, prove it fits (memory_analysis), and extract
the roofline raw terms (trip-count-aware HLO analysis + cost_analysis).

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k --variant pod_compressed

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>[__<variant>].json —
consumed by benchmarks/roofline.py and EXPERIMENTS.md §Dry-run/§Roofline.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config, input_specs
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.transformer import init_params, param_count
from repro.optim import adam
from repro.parallel.sharding import batch_specs, param_specs
from repro.train import TrainerConfig, init_train_state, make_train_step

# TPU v5e hardware constants (per chip).
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # B/s
LINK_BW = 50e9           # B/s per ICI link

# gradient-accumulation chunks per arch for the train_4k cell (activation
# memory control; batch 256 must stay divisible by microbatches × DP shards).
MICROBATCHES = {
    "granite-20b": 16, "yi-9b": 8, "llama-3.2-vision-11b": 8,
    "qwen3-moe-30b-a3b": 8, "deepseek-moe-16b": 8, "gemma3-4b": 4,
    "hubert-xlarge": 4, "olmo-1b": 2, "zamba2-1.2b": 4, "mamba2-370m": 8,
}

# chunked prefill (steps.make_prefill_step): top-k MoE dispatch at 1M prompt
# tokens needs sequence-chunking to fit HBM.
PREFILL_CHUNKS = {"qwen3-moe-30b-a3b": 4, "deepseek-moe-16b": 2}

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _sharded_specs(tree, spec_tree, mesh):
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, spec_tree,
    )


def _pad_spec(spec: P, ndim: int, prefix=()) -> P:
    entries = tuple(prefix) + tuple(spec) + (None,) * (ndim - len(prefix) - len(spec))
    return P(*entries[:ndim])


def _train_state_specs(cfg, tcfg, optimizer, mesh, n_pods):
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, tcfg, optimizer, k, n_pods=n_pods),
        jax.random.PRNGKey(0),
    )
    pspecs = param_specs(cfg, mesh)
    wq_specs = jax.tree_util.tree_map(lambda w: P(), state.wq) if state.wq is not None else None
    opt_specs = {"step": P(), "m": pspecs, "v": pspecs}
    res_specs = None
    if state.residuals is not None:
        res_specs = jax.tree_util.tree_map(
            lambda r, s: _pad_spec(s, r.ndim, prefix=("pod",)),
            state.residuals, pspecs,
        )
    spec_state = dataclasses.replace(
        state,
        params=pspecs, wq=wq_specs, opt_state=opt_specs,
        residuals=res_specs, step=P(),
    )
    sharded = jax.tree_util.tree_map(
        lambda l, s: None if l is None else jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        state, spec_state,
        is_leaf=lambda x: x is None,
    )
    return sharded


def active_param_count(cfg) -> int:
    """N_active: MoE counts only top-k routed experts (6·N_active·D)."""
    n = param_count(cfg)
    if cfg.n_experts:
        per_expert = 3 * cfg.d_model * cfg.moe_d_ff
        inactive = cfg.n_layers * (cfg.n_experts - cfg.top_k) * per_expert
        n -= inactive
    return n


def model_flops(cfg, shape_name: str) -> float:
    spec = SHAPES[shape_name]
    n_act = active_param_count(cfg)
    d_tokens = spec.global_batch * spec.seq_len
    if spec.kind == "train":
        return 6.0 * n_act * d_tokens
    if spec.kind == "prefill":
        return 2.0 * n_act * d_tokens
    return 2.0 * n_act * spec.global_batch  # decode: one token per request


def build_cell(arch: str, shape_name: str, mesh, variant: str):
    """Returns (jitted_fn, example_args_specs, meta)."""
    spec = SHAPES[shape_name]
    is_train = spec.kind == "train"
    flags = set(variant.split("+")) if variant else {"baseline"}
    axes_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pods = axes_sizes.get("pod", 1)
    # batch-carrying mesh axes for activation constraints; inside the
    # compressed (manual-over-pod) step only "data" remains auto.
    if "pod_compressed" in flags:
        bax = ("data",)
    else:
        bax = tuple(a for a in ("pod", "data") if a in axes_sizes)
    n_batch_shards = int(np.prod([axes_sizes[a] for a in bax])) if bax else 1
    if spec.global_batch % max(n_batch_shards, 1) or spec.global_batch < n_batch_shards:
        bax = ()  # e.g. long_500k batch=1: sequence-parallel cache instead
    cfg = get_config(
        arch,
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        remat="full" if is_train else "none",
        mesh_batch_axes=bax,
        mesh_ep_axis="model",
        # optimized defaults (§Perf A): shard_map all_to_all dispatch with
        # int8 wire; "moe_gspmd" / "moe_bf16" flags select the older paths.
        moe_impl="gspmd" if "moe_gspmd" in flags else "a2a",
        moe_wire="bf16" if "moe_bf16" in flags else "int8",
    )
    ispecs = input_specs(cfg, shape_name)
    bspecs = batch_specs(cfg, shape_name, mesh)
    batch_sharded = _sharded_specs(ispecs, bspecs, mesh)

    if is_train:
        # clamp: each microbatch must still cover every batch shard
        # (multi-pod halves the per-shard batch vs single-pod).
        micro = MICROBATCHES.get(arch, 1)
        if bax:
            micro = min(micro, spec.global_batch // n_batch_shards)
        tcfg = TrainerConfig(
            qat=True,
            pod_compression=("pod_compressed" in flags),
            error_feedback=("pod_compressed" in flags),
            microbatches=max(micro, 1),
        )
        optimizer = adam(1e-4)
        step = make_train_step(cfg, tcfg, optimizer, mesh)
        state_specs = _train_state_specs(cfg, tcfg, optimizer, mesh, n_pods)
        fn = jax.jit(step)
        args = (state_specs, batch_sharded)
    else:
        pspecs = param_specs(cfg, mesh)
        params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        params_sharded = _sharded_specs(params, pspecs, mesh)
        if spec.kind == "prefill":
            step = make_prefill_step(cfg, max_seq=spec.seq_len,
                                     chunks=PREFILL_CHUNKS.get(arch, 1))
            fn = jax.jit(step)
        else:
            step = make_decode_step(cfg)
            fn = jax.jit(step, donate_argnums=(1,))
        args = (params_sharded, batch_sharded)
    return fn, args, cfg


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str = "baseline",
             out_dir: str = ARTIFACT_DIR) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(mesh.devices.size)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "mesh_axes": describe(mesh)["axes"],
        "n_devices": n_dev,
    }
    try:
        fn, args, cfg = build_cell(arch, shape_name, mesh, variant)
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = analyze_hlo(compiled.as_text())

        flops_dev = hlo["flops_per_device"]
        bytes_dev = hlo["bytes_per_device"]
        coll_dev = hlo["collective_bytes_per_device"]
        compute_s = flops_dev / PEAK_FLOPS
        memory_s = bytes_dev / HBM_BW
        collective_s = coll_dev / LINK_BW
        terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
        bottleneck = max(terms, key=terms.get)
        mflops = model_flops(cfg, shape_name)
        record.update({
            "status": "ok",
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "param_count": param_count(cfg),
            "active_param_count": active_param_count(cfg),
            "memory": {
                "argument_bytes_per_device": ma.argument_size_in_bytes,
                "output_bytes_per_device": ma.output_size_in_bytes,
                "temp_bytes_per_device": ma.temp_size_in_bytes,
                "alias_bytes_per_device": ma.alias_size_in_bytes,
                "peak_estimate_gb": round(
                    (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 1e9, 3),
            },
            "hlo": {
                "flops_per_device": flops_dev,
                "bytes_per_device": bytes_dev,
                "collective_bytes_per_device": coll_dev,
                "collective_breakdown": hlo["collective_breakdown"],
                "n_collective_ops_executed": hlo["n_collective_ops_executed"],
                "while_trip_counts": hlo["while_trip_counts"],
                "xla_cost_analysis_flops": ca.get("flops"),
            },
            "roofline": {
                "compute_term_s": compute_s,
                "memory_term_s": memory_s,
                "collective_term_s": collective_s,
                "bottleneck": bottleneck,
                "step_time_lower_bound_s": max(terms.values()),
                "model_flops": mflops,
                "useful_flops_ratio": (
                    mflops / (flops_dev * n_dev) if flops_dev else None
                ),
                "mfu_upper_bound": (
                    mflops / (max(terms.values()) * n_dev * PEAK_FLOPS)
                    if max(terms.values()) > 0 else None
                ),
            },
        })
    except Exception as e:  # record failures — they are bugs to fix
        record.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        })
    os.makedirs(out_dir, exist_ok=True)
    tag = "" if variant == "baseline" else f"__{variant}"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}{tag}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=float)
    return record


def cells(mesh_kinds=("single", "multi")):
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape_name in SHAPES:
            ok, reason = applicable(cfg, shape_name)
            if not ok:
                continue
            for mk in mesh_kinds:
                yield arch, shape_name, mk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    if args.list:
        for c in cells():
            print(*c)
        return

    if args.all:
        todo = list(cells())
        if args.only_missing:
            def missing(c):
                p = os.path.join(args.out, f"{c[0]}__{c[1]}__{c[2]}.json")
                if not os.path.exists(p):
                    return True
                with open(p) as f:
                    return json.load(f).get("status") != "ok"
            todo = [c for c in todo if missing(c)]
        for arch, shape_name, mk in todo:
            r = run_cell(arch, shape_name, mk, out_dir=args.out)
            rf = r.get("roofline", {})
            print(f"[{r['status']:5s}] {arch} × {shape_name} × {mk} "
                  f"compile={r.get('compile_s', '-')}s "
                  f"bottleneck={rf.get('bottleneck', '-')} "
                  f"peak_gb={r.get('memory', {}).get('peak_estimate_gb', '-')}",
                  flush=True)
            if r["status"] != "ok":
                print(r.get("error"), flush=True)
        return

    r = run_cell(args.arch, args.shape, args.mesh, args.variant, out_dir=args.out)
    print(json.dumps(r, indent=1, default=float))


if __name__ == "__main__":
    main()
