"""Distributed train-step factory.

The paper-faithful QAT path: the loss is evaluated on FTTQ-quantized params
(clients train the quantized network — Alg. 1); latent full-precision params
and the per-layer trained factors w_q update from STE gradients.

Distribution:
  - single-pod mesh ("data","model"): plain jit + GSPMD (FSDP/TP/EP per
    parallel.sharding).
  - multi-pod mesh ("pod","data","model") with pod_compression=True: the
    step is shard_map'ed MANUAL over "pod" (auto over "data"/"model");
    per-pod gradients are synchronized with the ternary-compressed
    all-gather collective (parallel.collectives) + error feedback — the
    T-FedAvg wire protocol at datacenter cadence. With
    pod_compression=False, params are replicated over "pod" and GSPMD emits
    a standard (exact) cross-pod all-reduce — the FedAvg-equivalent baseline.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fttq
from repro.models import transformer as tfm
from repro.optim import Optimizer, apply_updates, clip_by_global_norm
from repro.parallel.collectives import ternary_allreduce_tree
from repro.parallel.sharding import logical_batch_axes, param_specs

Pytree = Any


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    qat: bool = True                     # train the quantized network (FTTQ)
    fttq: fttq.FTTQConfig = dataclasses.field(default_factory=fttq.FTTQConfig)
    grad_clip: float = 1.0
    wq_lr: float = 0.05
    pod_compression: bool = True         # ternary cross-pod grad sync
    error_feedback: bool = True
    microbatches: int = 1                # gradient-accumulation chunks


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Pytree
    wq: Pytree
    opt_state: Pytree
    residuals: Pytree | None
    step: jax.Array


def init_train_state(
    model_cfg: tfm.ModelConfig,
    tcfg: TrainerConfig,
    optimizer: Optimizer,
    key: jax.Array,
    *,
    n_pods: int = 1,
) -> TrainState:
    params = tfm.init_params(model_cfg, key)
    wq = fttq.init_wq_tree(params, tcfg.fttq) if tcfg.qat else None
    opt_state = optimizer.init(params)
    residuals = None
    if tcfg.pod_compression and n_pods > 1 and tcfg.error_feedback:
        # per-pod error-feedback residuals, stacked on a leading pod axis.
        residuals = jax.tree_util.tree_map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params
        )
    return TrainState(
        params=params, wq=wq, opt_state=opt_state, residuals=residuals,
        step=jnp.zeros((), jnp.int32),
    )


def _loss(model_cfg, tcfg, params, wq, batch):
    if tcfg.qat:
        qparams = fttq.quantize_tree(params, wq, tcfg.fttq)
    else:
        qparams = params
    loss, metrics = tfm.loss_fn(model_cfg, qparams, batch)
    return loss, metrics


def _apply_grads(tcfg, optimizer, state: TrainState, grads, g_wq, loss, metrics,
                 residuals=None):
    grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
    updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
    params = apply_updates(state.params, updates)
    if tcfg.qat:
        def upd_wq(w, g, p):
            if w is None:
                return None
            # float(p.size): stacked expert weights exceed int32 (>2^31
            # elements) and an int literal would overflow jit arg parsing.
            return (w - tcfg.wq_lr * g / float(p.size)).astype(w.dtype)

        wq = jax.tree_util.tree_map(
            upd_wq, state.wq, g_wq, state.params, is_leaf=lambda x: x is None
        )
    else:
        wq = state.wq
    new_state = TrainState(
        params=params, wq=wq, opt_state=opt_state,
        residuals=residuals if residuals is not None else state.residuals,
        step=state.step + 1,
    )
    out_metrics = {"loss": loss, "grad_norm": gnorm, **metrics}
    return new_state, out_metrics


def make_train_step(
    model_cfg: tfm.ModelConfig,
    tcfg: TrainerConfig,
    optimizer: Optimizer,
    mesh=None,
):
    """Returns step(state, batch) → (state, metrics). jit it with the
    shardings from launch.dryrun / launch.train."""

    multi_pod = mesh is not None and "pod" in mesh.axis_names
    compressed = multi_pod and tcfg.pod_compression
    # batch mesh axes visible to the microbatch reshape. In the compressed
    # path the step body runs inside a shard_map MANUAL over "pod", so only
    # "data" remains an auto axis there.
    if mesh is None:
        mb_axes: tuple = ()
    elif compressed:
        mb_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    else:
        mb_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def _constrain_mb(x):
        if not mb_axes or x.ndim < 2:
            return x
        u = jax.sharding.PartitionSpec.UNCONSTRAINED
        spec = P(None, mb_axes, *([u] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(x, spec)

    def grads_of(state: TrainState, batch):
        if tcfg.qat:
            (loss, metrics), (g_p, g_w) = jax.value_and_grad(
                lambda p, w: _loss(model_cfg, tcfg, p, w, batch),
                argnums=(0, 1), has_aux=True,
            )(state.params, state.wq)
        else:
            (loss, metrics), g_p = jax.value_and_grad(
                lambda p: _loss(model_cfg, tcfg, p, None, batch), has_aux=True
            )(state.params)
            g_w = None
        return loss, metrics, g_p, g_w

    def local_grads(state: TrainState, batch):
        """Microbatched gradient accumulation: batch (B, …) is processed as
        ``microbatches`` sequential chunks (lax.scan), grads averaged. Keeps
        live activations at 1/microbatches — the standard way the 4k-train
        cells fit HBM with remat (DESIGN.md §4)."""
        n_micro = tcfg.microbatches
        if n_micro <= 1:
            return grads_of(state, batch)

        def split(x):
            b = x.shape[0]
            return _constrain_mb(x.reshape(n_micro, b // n_micro, *x.shape[1:]))

        mb = jax.tree_util.tree_map(split, batch)

        def body(acc, mbatch):
            loss, metrics, g_p, g_w = grads_of(state, mbatch)
            a_l, a_m, a_p, a_w = acc
            add = lambda a, g: a + g.astype(a.dtype) / n_micro
            acc = (
                a_l + loss / n_micro,
                jax.tree_util.tree_map(lambda a, g: a + g / n_micro, a_m, metrics),
                jax.tree_util.tree_map(add, a_p, g_p),
                jax.tree_util.tree_map(
                    lambda a, g: None if a is None else a + g / n_micro,
                    a_w, g_w, is_leaf=lambda x: x is None,
                ) if g_w is not None else None,
            )
            return acc, None

        l0 = jnp.zeros((), jnp.float32)
        m0 = {"ce": jnp.zeros((), jnp.float32), "aux": jnp.zeros((), jnp.float32)}
        p0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        w0 = (
            jax.tree_util.tree_map(
                lambda w: None if w is None else jnp.zeros(w.shape, jnp.float32),
                state.wq, is_leaf=lambda x: x is None,
            )
            if tcfg.qat else None
        )
        (loss, metrics, g_p, g_w), _ = jax.lax.scan(body, (l0, m0, p0, w0), mb)
        return loss, metrics, g_p, g_w

    if not compressed:
        def step(state: TrainState, batch):
            loss, metrics, g_p, g_w = local_grads(state, batch)
            # cross-pod sync (if any) is GSPMD's exact all-reduce (baseline).
            return _apply_grads(tcfg, optimizer, state, g_p, g_w, loss, metrics)

        return step

    # ---- compressed multi-pod path --------------------------------------
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
    batch_axes = P("pod")

    def per_pod_step(state: TrainState, residuals, batch):
        # residuals arrive with a leading length-1 pod-block dim.
        residuals = jax.tree_util.tree_map(lambda r: r[0], residuals)
        loss, metrics, g_p, g_w = local_grads(state, batch)
        g_p, new_res = ternary_allreduce_tree(
            g_p, "pod", cfg=tcfg.fttq, residuals=residuals,
            error_feedback=tcfg.error_feedback,
        )
        if g_w is not None:
            g_w = jax.tree_util.tree_map(
                lambda g: None if g is None else jax.lax.pmean(g, "pod"),
                g_w, is_leaf=lambda x: x is None,
            )
        loss = jax.lax.pmean(loss, "pod")
        metrics = jax.tree_util.tree_map(lambda m: jax.lax.pmean(m, "pod"), metrics)
        new_state, out_metrics = _apply_grads(
            tcfg, optimizer, state, g_p, g_w, loss, metrics
        )
        new_res = jax.tree_util.tree_map(lambda r: r[None], new_res)
        return new_state, new_res, out_metrics

    def step(state: TrainState, batch):
        residuals = state.residuals
        state = dataclasses.replace(state, residuals=None)
        batch_specs = jax.tree_util.tree_map(
            lambda _: P("pod"), batch
        )
        state_specs = jax.tree_util.tree_map(lambda _: P(), state)
        res_specs = jax.tree_util.tree_map(lambda _: P("pod"), residuals)
        new_state, new_res, metrics = jax.shard_map(
            per_pod_step,
            mesh=mesh,
            in_specs=(state_specs, res_specs, batch_specs),
            out_specs=(
                jax.tree_util.tree_map(lambda _: P(), state),
                res_specs,
                jax.tree_util.tree_map(lambda _: P(), {"loss": 0.0, "grad_norm": 0.0,
                                                       "ce": 0.0, "aux": 0.0}),
            ),
            axis_names={"pod"},
            check_vma=False,
        )(state, residuals, batch)
        new_state = dataclasses.replace(new_state, residuals=new_res)
        return new_state, metrics

    return step
