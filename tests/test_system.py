"""End-to-end behaviour tests: the paper's headline claims on synthetic data
plus a reduced-mesh dry-run integration check (8 host devices, subprocess)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tfedavg_matches_fedavg_accuracy_at_16x_less_comms():
    """Paper Tables II+IV in one: T-FedAvg reaches comparable accuracy to
    FedAvg with ~15× less measured communication."""
    from repro.data import partition_iid, synthetic_classification
    from repro.fed import FedConfig, run_federated
    from repro.models.paper_models import init_mlp_mnist, mlp_mnist
    from repro.optim import adam

    x, y, xt, yt = synthetic_classification(
        jax.random.PRNGKey(0), 2000, 10, 784, noise=3.0, n_test=500
    )
    clients = partition_iid(x, y, 5)
    params = init_mlp_mnist(jax.random.PRNGKey(1))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)

    def eval_fn(p):
        logits = mlp_mnist(p, xt_j)
        acc = jnp.mean(jnp.argmax(logits, -1) == yt_j)
        return float(acc), 0.0

    results = {}
    for algo in ("fedavg", "tfedavg"):
        cfg = FedConfig(algorithm=algo, participation=1.0, local_epochs=2,
                        batch_size=32, rounds=8)
        results[algo] = run_federated(mlp_mnist, params, clients, cfg,
                                      adam(1e-3), eval_fn, eval_every=8)
    acc_fp = results["fedavg"].accuracy[-1]
    acc_t = results["tfedavg"].accuracy[-1]
    ratio = results["fedavg"].upload_bytes / results["tfedavg"].upload_bytes
    assert acc_t > 0.85 * acc_fp, (acc_t, acc_fp)
    assert ratio > 10, ratio


def test_qat_lm_training_learns():
    """The paper's technique on a modern LM: FTTQ-QAT pretraining reduces
    loss on a synthetic token stream."""
    from repro.data.synthetic import synthetic_tokens, token_batches
    from repro.models.transformer import ModelConfig
    from repro.optim import adam
    from repro.train import TrainerConfig, init_train_state, make_train_step

    cfg = ModelConfig(name="lm", family="dense", n_layers=2, d_model=64,
                      vocab_size=64, n_heads=4, n_kv_heads=2, d_ff=128)
    tcfg = TrainerConfig(qat=True, pod_compression=False)
    opt = adam(3e-3)
    state = init_train_state(cfg, tcfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg, opt))
    toks = synthetic_tokens(jax.random.PRNGKey(1), 30_000, vocab=64)
    it = token_batches(toks, batch=8, seq=32)
    losses = []
    for _ in range(30):
        batch, _ = next(it)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


@pytest.mark.slow
def test_reduced_mesh_dryrun_integration():
    """The dry-run machinery end-to-end on an 8-device mesh (subprocess)."""
    code = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.configs as C
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.steps import make_decode_step
    from repro.models.transformer import init_params, init_cache
    from repro.parallel.sharding import batch_specs, param_specs

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = C.get_reduced("yi-9b", mesh_batch_axes=("data",),
                        param_dtype="bfloat16", compute_dtype="bfloat16")
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    pspecs = param_specs(cfg, mesh)
    sh = lambda t, s: jax.tree_util.tree_map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)), t, s)
    params_sh = sh(params, pspecs)
    b, smax = 8, 64
    cache = jax.eval_shape(lambda: init_cache(cfg, b, smax, jnp.bfloat16))
    cache_sh = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
            sharding=NamedSharding(mesh, P(None, "data", None, None, None))), cache)
    batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32,
                 sharding=NamedSharding(mesh, P("data", None))),
             "cache": cache_sh,
             "pos": jax.ShapeDtypeStruct((), jnp.int32,
                 sharding=NamedSharding(mesh, P()))}
    step = make_decode_step(cfg)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(params_sh, batch).compile()
    ma = compiled.memory_analysis()
    r = analyze_hlo(compiled.as_text())
    assert r["flops_per_device"] > 0
    print("DRYRUN_OK", ma.temp_size_in_bytes, r["flops_per_device"])
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_OK" in out.stdout


def test_hlo_analyzer_against_xla_cost_analysis():
    """On a while-free program, the analyzer must agree with XLA's own
    FLOP count to within 5% (it counts dots; XLA adds elementwise)."""
    from repro.launch.hlo_analysis import analyze_hlo

    def f(w1, w2, x):
        return jnp.sum(jax.nn.gelu(x @ w1) @ w2)

    w1 = jax.ShapeDtypeStruct((128, 512), jnp.float32)
    w2 = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    comp = jax.jit(f).lower(w1, w2, x).compile()
    mine = analyze_hlo(comp.as_text())["flops_per_device"]
    xla = comp.cost_analysis()["flops"]
    assert abs(mine - xla) / xla < 0.05


def test_hlo_analyzer_scan_trip_counts():
    from repro.launch.hlo_analysis import analyze_hlo

    def scanned(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    comp = jax.jit(scanned).lower(x, w).compile()
    r = analyze_hlo(comp.as_text())
    assert r["flops_per_device"] == pytest.approx(12 * 2 * 64**3, rel=0.01)
    assert 12 in r["while_trip_counts"].values()


def test_paper_models_forward():
    from repro.models.paper_models import (
        init_mlp_mnist, init_resnet_cifar, mlp_mnist, resnet_cifar,
    )

    p = init_mlp_mnist(jax.random.PRNGKey(0))
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(p))
    assert n_params == 24330  # paper Table I
    out = mlp_mnist(p, jnp.ones((4, 784)))
    assert out.shape == (4, 10)

    rp = init_resnet_cifar(jax.random.PRNGKey(1))
    logits = resnet_cifar(rp, jnp.ones((2, 32, 32, 3)))
    assert logits.shape == (2, 10)
    assert not bool(jnp.any(jnp.isnan(logits)))


def test_compile_cache_dir_from_env_or_fixed_checkout_path(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins; without it the cache sits at one
    fixed path under the checkout, whatever the cwd or the pid."""
    from repro.launch.env import configure_compile_cache

    fixed = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)
        assert configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # another process, another cwd and pid: the same path
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", "from repro.launch.env import "
         "configure_compile_cache as c; print(c())"],
        cwd=tmp_path, env=env, capture_output=True, text=True, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == fixed
