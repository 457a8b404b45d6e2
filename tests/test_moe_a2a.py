"""Regression tests for the optimized MoE dispatch (models/moe_a2a.py) —
the §Perf A optimization: shard_map + all_to_all with optional int8 wire.

Run in an 8-device subprocess (like test_parallel.py)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_a2a_matches_gspmd_dropfree():
    """At drop-free capacity the a2a dispatch must equal the GSPMD scatter
    dispatch EXACTLY (same expert math, same routing)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    import repro.configs as C
    from repro.models.transformer import init_params, forward
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    base = dict(capacity_factor=16.0, mesh_batch_axes=("data",),
                mesh_ep_axis="model")
    cfg_g = C.get_reduced("qwen3-moe-30b-a3b", moe_impl="gspmd", **base)
    cfg_a = C.get_reduced("qwen3-moe-30b-a3b", moe_impl="a2a", **base)
    params = init_params(cfg_g, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg_g.vocab_size)
    with jax.set_mesh(mesh):
        lg, _, _ = jax.jit(lambda p, t: forward(cfg_g, p, t))(params, toks)
        la, _, _ = jax.jit(lambda p, t: forward(cfg_a, p, t))(params, toks)
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(la, np.float32), rtol=2e-3, atol=2e-3)
    print("A2A_EXACT_OK", float(jnp.max(jnp.abs(lg - la))))
    """
    assert "A2A_EXACT_OK" in run_with_devices(code)


def test_a2a_int8_wire_close_and_trains():
    """int8 dispatch wire stays close to the bf16 wire and training steps
    converge (grads flow through quantized_all_to_all's custom VJP)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    import repro.configs as C
    from repro.models.transformer import init_params, forward
    from repro.train import TrainerConfig, init_train_state, make_train_step
    from repro.optim import adam
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    base = dict(capacity_factor=16.0, mesh_batch_axes=("data",),
                mesh_ep_axis="model", moe_impl="a2a")
    cfg_bf = C.get_reduced("deepseek-moe-16b", moe_wire="bf16", **base)
    cfg_q8 = C.get_reduced("deepseek-moe-16b", moe_wire="int8", **base)
    params = init_params(cfg_bf, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg_bf.vocab_size)
    with jax.set_mesh(mesh):
        lb, _, _ = jax.jit(lambda p, t: forward(cfg_bf, p, t))(params, toks)
        lq, _, _ = jax.jit(lambda p, t: forward(cfg_q8, p, t))(params, toks)
    rel = float(jnp.linalg.norm(lb - lq) / (jnp.linalg.norm(lb) + 1e-9))
    assert rel < 0.05, rel  # int8 per-slot scales: ≲1% typical

    tcfg = TrainerConfig(qat=True, pod_compression=False)
    opt = adam(2e-3)
    state = init_train_state(cfg_q8, tcfg, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg_q8, tcfg, opt, mesh)
    batch = {"tokens": toks, "labels": jax.random.randint(
        jax.random.PRNGKey(2), (4, 16), 0, cfg_q8.vocab_size)}
    with jax.set_mesh(mesh):
        js = jax.jit(step)
        s, m0 = js(state, batch)
        for _ in range(4):
            s, m = js(s, batch)
    assert float(m["loss"]) < float(m0["loss"])
    print("Q8_WIRE_OK", rel, float(m0["loss"]), float(m["loss"]))
    """
    assert "Q8_WIRE_OK" in run_with_devices(code)


def test_quantized_all_to_all_roundtrip_error():
    """Unit bound: per-slot int8 quantization error ≤ scale/2 elementwise."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.models.moe_a2a import quantized_all_to_all
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("model",))
    # per-device block (4, 8, 32): dim 0 divisible by the 4-way a2a.
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8, 32))

    def f(x):
        return quantized_all_to_all(x, "model")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                            out_specs=P("model"), axis_names={"model"},
                            check_vma=False))(x)
    # tiled a2a permutes blocks between devices; with 1 block/device the
    # global array is a permutation of slot groups — check VALUES survive
    # quantization: every output row matches SOME input row within bound.
    xs = np.asarray(x).reshape(-1, 32)
    os_ = np.asarray(out).reshape(-1, 32)
    scale = np.abs(xs).max(-1) / 127.0
    for row, o in enumerate(os_):
        d = np.abs(xs - o).max(-1)
        assert (d <= scale * 0.51 + 1e-6).any(), row
    print("QA2A_BOUND_OK")
    """
    assert "QA2A_BOUND_OK" in run_with_devices(code)
