"""Distribution-layer tests: sharding rules, compressed collectives, and the
multi-pod trainer — run in a subprocess with 8 forced host devices so the
rest of the suite keeps the real single-device view."""

import json
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


def test_param_specs_cover_tree():
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    import repro.configs as C
    from repro.parallel.sharding import param_specs
    from repro.models.transformer import init_params
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    for arch in C.ARCH_IDS:
        cfg = C.get_config(arch)
        shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        specs = param_specs(cfg, mesh)
        assert (jax.tree_util.tree_structure(shapes)
                == jax.tree_util.tree_structure(specs)), arch
        # every spec entry is valid for its shape
        for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_leaves(specs),
        ):
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for d, entry in enumerate(spec):
                if entry is None:
                    continue
                assert leaf.shape[d] % sizes[entry] == 0, (arch, path, spec)
    print("SPECS_OK")
    """
    assert "SPECS_OK" in run_with_devices(code)


def test_ternary_allreduce_approximates_mean():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel.collectives import ternary_allreduce
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))

    def f(x):
        out, _ = ternary_allreduce(x[0], "pod", residual=None)
        return out

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                            out_specs=P(), axis_names={"pod"},
                            check_vma=False))(x)
    true_mean = jnp.mean(x, axis=0)
    # ternary mean correlates with true mean (quantized, not exact)
    a = np.asarray(out).ravel(); b = np.asarray(true_mean).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.5, corr
    print("ALLREDUCE_OK")
    """
    assert "ALLREDUCE_OK" in run_with_devices(code)


def test_multipod_compressed_training_converges():
    code = """
    import jax, jax.numpy as jnp
    from repro.models.transformer import ModelConfig
    from repro.train import TrainerConfig, make_train_step, init_train_state
    from repro.optim import adam
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    losses = {}
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128)}
    for compressed in (False, True):
        tcfg = TrainerConfig(qat=True, pod_compression=compressed,
                             error_feedback=True)
        opt = adam(2e-3)
        state = init_train_state(cfg, tcfg, opt, jax.random.PRNGKey(0), n_pods=2)
        step = make_train_step(cfg, tcfg, opt, mesh)
        with jax.set_mesh(mesh):
            js = jax.jit(step)
            tr = []
            for _ in range(6):
                state, m = js(state, batch)
                tr.append(float(m["loss"]))
        losses[compressed] = tr
    # both converge; compressed stays within 25% of exact after 6 steps
    assert losses[False][-1] < losses[False][0]
    assert losses[True][-1] < losses[True][0]
    assert losses[True][-1] < losses[False][-1] * 1.25
    print("MULTIPOD_OK", losses)
    """
    assert "MULTIPOD_OK" in run_with_devices(code)


def test_elastic_remesh_after_pod_loss():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.transformer import ModelConfig
    from repro.optim import adam
    from repro.train import TrainerConfig, init_train_state, make_train_step
    from repro.train.fault import elastic_reshard
    from repro.parallel.sharding import param_shardings
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128)
    tcfg = TrainerConfig(qat=False, pod_compression=False)
    opt = adam(1e-3)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128)}

    # train on the 2-"pod" mesh
    from repro.launch.mesh import make_mesh
    mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    state = init_train_state(cfg, tcfg, opt, jax.random.PRNGKey(0))
    with jax.set_mesh(mesh2):
        step2 = jax.jit(make_train_step(cfg, tcfg, opt, mesh2))
        state, m2 = step2(state, batch)

    # "pod failure": rebuild a 1-pod (4-device) mesh, reshard the WHOLE
    # state (params + optimizer moments + scalars), continue
    mesh1 = make_mesh((2, 2), ("data", "model"))
    shard1 = param_shardings(cfg, mesh1)
    host = jax.device_get(state)
    repl = NamedSharding(mesh1, P())
    import dataclasses
    state1 = dataclasses.replace(
        host,
        params=elastic_reshard(host.params, shard1),
        opt_state={"step": jax.device_put(host.opt_state["step"], repl),
                   "m": elastic_reshard(host.opt_state["m"], shard1),
                   "v": elastic_reshard(host.opt_state["v"], shard1)},
        step=jax.device_put(host.step, repl),
    )
    with jax.set_mesh(mesh1):
        step1 = jax.jit(make_train_step(cfg, tcfg, opt, mesh1))
        state1, m1 = step1(state1, batch)
    assert np.isfinite(float(m1["loss"]))
    print("ELASTIC_OK", float(m2["loss"]), float(m1["loss"]))
    """
    assert "ELASTIC_OK" in run_with_devices(code)
