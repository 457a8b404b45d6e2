"""Chip smoke test: the T-FedAvg round and packed serving, once, on one TPU.

    python chip_smoke.py

One process, no subprocess, three phases in order:

  1. device — configure the compile cache, then refuse to go on unless JAX's
     first device is a TPU;
  2. federated round — ResNet18* at the paper's width (64 channels) on a
     seeded CIFAR-shaped set (50,000 train / 10,000 test images), 10
     non-IID clients with N_c = 2, λ = 1, E = 1, B = 64: three synchronous
     T-FedAvg rounds through ``repro.fed.run_federated`` with the fused
     encode and the fused fan-in. A check round from the trained global
     model then tests (a) the fused upload against the reference encode,
     (b) the streaming ``Aggregator`` against ``server_aggregate`` on the
     same blobs and (c) that the encode and fan-in kernels lower to Mosaic
     custom calls rather than to the interpreter;
  3. packed serving — olmo-1b at its published config through
     ``launch.serve``: ``ternary_deploy(packed=True)``, the logits gap to the
     dense reference decoded from the same wire blob, a 4×32 prefill and 8
     decode steps, and the device's peak memory.

Every failed check raises, so the last line of standard output,
``{"ok": true, "device": {...}}``, is printed only after all of them pass.
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0
N_CLIENTS = 10
ROUNDS = 3


def logits_rel_bound(cfg) -> float:
    """Bound on the packed-vs-dequant logits gap, relative to the largest
    reference logit. Both forwards run at f32 matmul precision
    (``packed_logits_gap``), so they differ only in accumulation order:
    2⁻²⁴ relative per rounding, growing as √K over a K-term dot, added over
    the residual stream's blocks and the head. A wrong code or layout moves
    logits by their own scale, a scale rounded to bf16 by 2⁻⁹."""
    return (cfg.n_layers + 1) * math.sqrt(max(cfg.d_model, cfg.d_ff)) * 2.0 ** -24


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def device_phase() -> dict:
    from repro.launch.env import configure_compile_cache

    cache = configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX's first device is "
                           f"{dev.platform} ({dev.device_kind})")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: {info} compile cache: {cache}", flush=True)
    return info


# --------------------------------------------------------------------------
# Federated round.
# --------------------------------------------------------------------------


def _code_mismatches(fused, ref, params, fcfg) -> int:
    """Wire trees that differ only in ternary codes that sit on the
    threshold: same tree, same w_q and raw leaves, and every differing code
    at a scaled weight within 1 ulp of Δ (as the reference computes both).
    Returns the number of differing codes."""
    import jax
    import numpy as np

    from repro.core import fttq
    from repro.core.ternary import TernaryTensor

    is_t = lambda x: isinstance(x, TernaryTensor)  # noqa: E731
    f_leaves, f_def = jax.tree_util.tree_flatten(fused, is_leaf=is_t)
    r_leaves, r_def = jax.tree_util.tree_flatten(ref, is_leaf=is_t)
    check(f_def == r_def, "(a) fused and reference payload trees differ")
    n_diff = 0
    for f, r, p in zip(f_leaves, r_leaves, jax.tree_util.tree_leaves(params)):
        if not is_t(r):
            check(np.array_equal(np.asarray(f), np.asarray(r)),
                  "(a) a raw payload leaf differs")
            continue
        check(np.array_equal(np.asarray(f.w_q), np.asarray(r.w_q)),
              "(a) a trained w_q differs")
        diff = np.asarray(f.ternary()) != np.asarray(r.ternary())
        if not diff.any():
            continue
        ts = fttq.scale_layer(p)
        delta = np.float32(fttq.fttq_threshold(ts, fcfg.t_k, fcfg.threshold_rule))
        off = np.abs(np.abs(np.asarray(ts)[diff]) - delta)
        check(bool(np.all(off <= np.spacing(delta))),
              f"(a) codes differ away from the threshold: max |θ_s − Δ| "
              f"{off.max():.3e} > 1 ulp {np.spacing(delta):.3e}")
        n_diff += int(diff.sum())
    return n_diff


def federated_phase(*, n_train: int = 50_000, n_test: int = 10_000,
                    width: int = 64):
    """Three T-FedAvg rounds and the check round; returns the trained
    tree of check (a)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.wire import decode_update, encode_update
    from repro.core.tfedavg import (
        TernaryUpdate, client_update_payload, fedavg_round_bytes,
        server_aggregate, tfedavg_round_bytes,
    )
    from repro.data import partition_noniid, synthetic_classification
    from repro.fed import Aggregator, FedConfig, run_federated
    from repro.fed.simulation import (
        broadcast_blob, local_train, make_local_steps, receive_broadcast,
        train_client,
    )
    from repro.models.paper_models import init_resnet_cifar, resnet_cifar
    from repro.optim import adam

    x, y, xt, yt = synthetic_classification(
        jax.random.PRNGKey(SEED), n_train, 10, 3072, image_hw=(32, 32, 3),
        n_test=n_test)
    clients = partition_noniid(x, y, N_CLIENTS, 2, seed=SEED)
    params = init_resnet_cifar(jax.random.PRNGKey(SEED + 1), width=width)
    n_params = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    print(f"federated: ResNet18* width {width}, {n_params} params, "
          f"{N_CLIENTS} clients × ~{n_train // N_CLIENTS} samples", flush=True)

    logits_fn = jax.jit(resnet_cifar)
    latest = {}         # the global model the last round committed

    def eval_fn(p):
        latest["params"] = p
        correct, nll = 0.0, 0.0
        for i in range(0, n_test, 1000):
            lg = logits_fn(p, jnp.asarray(xt[i:i + 1000]))
            yb = jnp.asarray(yt[i:i + 1000])
            correct += float(jnp.sum(jnp.argmax(lg, -1) == yb))
            logp = jax.nn.log_softmax(lg, -1)
            nll -= float(jnp.sum(jnp.take_along_axis(logp, yb[:, None], -1)))
        return correct / n_test, nll / n_test

    cfg = FedConfig(algorithm="tfedavg", mode="sync", n_clients=N_CLIENTS,
                    participation=1.0, local_epochs=1, batch_size=64,
                    rounds=ROUNDS, seed=SEED)
    opt = adam(1e-3)
    res = run_federated(resnet_cifar, params, clients, cfg, opt, eval_fn,
                        eval_every=1)
    up = res.telemetry["upload_bytes_per_round"]
    down = res.telemetry["download_bytes_per_round"]
    for r in range(ROUNDS):
        print(f"round {r}: loss {res.loss[r]:.4f} acc {res.accuracy[r]:.4f} "
              f"upload {up[r]} B download {down[r]} B", flush=True)
        check(np.isfinite(res.loss[r]), f"round {r}: loss is not finite")
        check(0.0 <= res.accuracy[r] <= 1.0, f"round {r}: accuracy out of range")
        # every round ships one ternary wire model per client each way
        want = tfedavg_round_bytes(params, N_CLIENTS, cfg.fttq)
        check(up[r] == want["upload"] and down[r] == want["download"],
              f"round {r}: wire bytes differ from T-FedAvg's {want}")
    fp32 = fedavg_round_bytes(params, N_CLIENTS)["upload"]
    print(f"upload compression vs fp32 FedAvg: {fp32 / up[-1]:.2f}×",
          flush=True)

    # ---- check round, from the trained global model ----------------------
    start = receive_broadcast(broadcast_blob(latest["params"], cfg))
    fp_step, qat_step = make_local_steps(resnet_cifar, opt, cfg)
    rng = np.random.default_rng(SEED + 7)
    blobs = [train_client(c, start, cfg, opt, fp_step, qat_step, rng)
             for c in clients]

    # (a) one client's trained tree: fused upload vs the reference chain
    trained, wq = local_train(clients[0], start, cfg, opt, fp_step, qat_step,
                              rng)
    fused = client_update_payload(trained, wq, cfg.fttq)
    ref = client_update_payload(trained, wq, cfg.fttq, fused=False)
    if encode_update(fused) == encode_update(ref):
        print("(a) fused upload byte-identical to the reference", flush=True)
    else:
        n = _code_mismatches(fused, ref, trained, cfg.fttq)
        print(f"(a) fused upload differs from the reference in {n} codes, "
              f"each within 1 ulp of Δ", flush=True)

    # (b) streaming fan-in vs the list-based reference, same blobs
    agg = Aggregator(chunk_c=cfg.agg_chunk_c)
    for c, b in zip(clients, blobs):
        agg.add(b, weight=len(c))
    got = agg.finalize()
    want = server_aggregate([
        TernaryUpdate(payload=decode_update(b), n_samples=len(c))
        for c, b in zip(clients, blobs)
    ])
    pairs = [(np.asarray(g), np.asarray(w)) for g, w in
             zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]
    err = max(float(np.max(np.abs(g - w))) for g, w in pairs)
    print(f"(b) Aggregator vs server_aggregate: max |Δ| = {err:.3e}", flush=True)
    check(all(np.allclose(g, w, rtol=1e-5, atol=1e-6) for g, w in pairs),
          f"(b) Aggregator differs from server_aggregate by {err:.3e}")
    return trained


def lowering_check(trained) -> None:
    """(c) the fused encode and the fan-in, at this round's shapes, lower
    to Mosaic custom calls under the runtime's own dispatch."""
    import jax
    import jax.numpy as jnp

    from repro.core import fttq
    from repro.core.ternary import packed_nbytes
    from repro.fed.aggregator import bucket_for
    from repro.kernels.aggregate import packed_weighted_sum, padded_rows
    from repro.kernels.ops import use_interpret
    from repro.kernels.quantize_pack import (
        BLOCK_S, LANES, quantize_pack_segments, staged_rows,
    )

    sizes = [int(l.size) for p, l in
             jax.tree_util.tree_flatten_with_path(trained)[0]
             if fttq.is_quantizable(p, l, fttq.FTTQConfig())]
    rows = sum(staged_rows(n) for n in sizes)
    staged = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    scal = jax.ShapeDtypeStruct((rows // BLOCK_S, 2), jnp.float32)
    c = bucket_for(N_CLIENTS, 16)
    stacked = jax.ShapeDtypeStruct(
        (c, padded_rows(packed_nbytes(max(sizes))), LANES), jnp.uint8)
    coeffs = jax.ShapeDtypeStruct((c,), jnp.float32)
    interp = use_interpret()
    for name, text in (
        (f"quantize_pack_segments {staged.shape}",
         quantize_pack_segments.lower(staged, scal, interpret=interp).as_text()),
        (f"packed_weighted_sum {stacked.shape}",
         packed_weighted_sum.lower(stacked, coeffs, interpret=interp).as_text()),
    ):
        found = "tpu_custom_call" in text
        print(f"(c) {name}: tpu_custom_call {found}", flush=True)
        check(found, f"(c) {name} did not lower to a Mosaic kernel")


# --------------------------------------------------------------------------
# Packed serving.
# --------------------------------------------------------------------------


def serving_phase(cfg) -> None:
    import jax
    import numpy as np

    from repro.core import FTTQConfig
    from repro.launch.serve import generate, packed_logits_gap, ternary_deploy
    from repro.models.transformer import init_params, param_count

    print(f"serving: {cfg.name}, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{param_count(cfg) / 1e6:.1f}M params", flush=True)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    served, blob, _, _ = ternary_deploy(params, FTTQConfig(), packed=True)
    del params          # the dense reference below is built from the blob
    print(f"edge checkpoint: {len(blob)} B on the wire", flush=True)
    probe = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0,
                               cfg.vocab_size)
    gap, scale = packed_logits_gap(cfg, served, blob, probe)
    bound = logits_rel_bound(cfg)
    print(f"packed-vs-dequant logits: max |Δ| = {gap:.3e} "
          f"(max |logit| {scale:.3e}, ratio {gap / scale:.3e}, bound "
          f"{bound:.3e})", flush=True)
    check(np.isfinite(gap) and gap <= bound * scale,
          f"packed logits differ from the dequantized reference by "
          f"{gap / scale:.3e} of the largest logit (bound {bound:.3e})")
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 cfg.vocab_size)
    tokens = np.asarray(generate(cfg, served, prompts, gen=9))
    check(tokens.shape == (4, 9), f"generated tokens have shape {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
          "generated tokens out of the vocabulary")
    print(f"sample tokens: {tokens[0].tolist()}", flush=True)


def main() -> int:
    info = device_phase()
    import jax

    from repro.configs import get_config

    trained = federated_phase()
    lowering_check(trained)
    serving_phase(get_config("olmo-1b"))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
