"""Expert-parallel MoE via shard_map + all_to_all — the optimized dispatch.

Why: the GSPMD scatter-based dispatch (moe.py) is correct and simple, but
XLA lowers the cross-shard scatter-add into FULL-BUFFER all-reduces of the
(E, C, D) dispatch buffer — measured 2.2 TB/device/step on
qwen3-moe × train_4k (EXPERIMENTS.md §Perf A). The inherent communication
of top-k dispatch is only the k routed copies of each token; this module
moves exactly that via all_to_all:

  per device (T_loc tokens, E_loc = E/n_ep experts):
    1. route locally; destination device = expert // E_loc,
    2. LOCAL scatter into a (n_ep, C_send, D) send buffer (+ an int32
       buffer carrying each slot's local-expert index; 0 = empty),
    3. tiled all_to_all over the EP axis (both buffers),
    4. LOCAL scatter by local-expert index → (E_loc, C_loc, D), grouped
       GEMMs (einsum over the local expert dim),
    5. all_to_all back, local gather + gate-weighted combine.

Capacity semantics: per-(src,dst) queue C_send = T_loc·k/n_ep·cf and
per-local-expert queue C_loc = recv/E_loc·cf; overflow drops (GShard
semantics, like moe.py but applied per queue).

Must run inside a shard_map that is MANUAL over (batch_axes ∪ {ep_axis});
``transformer.forward`` arranges that when cfg.moe_impl == "a2a".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import act_fn


# ---------------------------------------------------------------------------
# int8-quantized all_to_all (beyond-paper, paper-inspired): the dispatch
# payload is activations, so we quantize each slot to int8 with a per-slot
# fp32 scale before it crosses the wire — 2× less EP traffic than bf16 (the
# paper's "compress what crosses the slow link" applied to expert routing).
# Backward quantizes the returning cotangents the same way (the tiled (0,0)
# all_to_all is its own transpose).
# ---------------------------------------------------------------------------


def _q8(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantized_all_to_all(x, axis: str):
    q, s = _q8(x)
    qq = jax.lax.all_to_all(q, axis, 0, 0, tiled=True)
    ss = jax.lax.all_to_all(s, axis, 0, 0, tiled=True)
    return (qq.astype(jnp.float32) * ss).astype(x.dtype)


def _qa2a_fwd(x, axis):
    return quantized_all_to_all(x, axis), None


def _qa2a_bwd(axis, _res, g):
    q, s = _q8(g)
    qq = jax.lax.all_to_all(q, axis, 0, 0, tiled=True)
    ss = jax.lax.all_to_all(s, axis, 0, 0, tiled=True)
    return ((qq.astype(jnp.float32) * ss).astype(g.dtype),)


quantized_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


def _fill_queue(values, dest, keep_mask, n_queues, capacity, extra=None):
    """Scatter values (N, D) into (n_queues, capacity, D) by dest (N,).

    Returns (buffer, pos, keep) where pos is each value's queue slot.
    extra: optional int payload (N,) scattered into (n_queues, capacity).
    """
    n = dest.shape[0]
    onehot = jax.nn.one_hot(dest, n_queues, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1  # (N,)
    keep = keep_mask & (pos < capacity)
    safe_pos = jnp.where(keep, pos, 0)
    safe_dest = jnp.where(keep, dest, 0)
    buf = jnp.zeros((n_queues, capacity) + values.shape[1:], values.dtype)
    buf = buf.at[safe_dest, safe_pos].add(
        jnp.where(keep.reshape((n,) + (1,) * (values.ndim - 1)), values, 0)
    )
    ebuf = None
    if extra is not None:
        ebuf = jnp.zeros((n_queues, capacity), jnp.int32)
        ebuf = ebuf.at[safe_dest, safe_pos].max(jnp.where(keep, extra, 0))
    return buf, ebuf, safe_pos, keep


def moe_a2a(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
    n_experts: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
    ep_axis: str = "model",
    data_axes: tuple = ("data",),
    wire_dtype: str = "bf16",   # "bf16" | "int8" dispatch payload
) -> tuple[jax.Array, jax.Array]:
    """x: (B_loc, S, D) per-shard activations → (out, aux). Call inside the
    manual shard_map region (transformer.forward sets it up)."""
    act = act_fn(activation)
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    n_ep = jax.lax.axis_size(ep_axis)
    e_loc = n_experts // n_ep

    # ---- 1. local routing (router weights are replicated) ----------------
    logits = (xt @ params["router"]).astype(jnp.float32)  # (T_loc, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)               # (T_loc, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # load-balance aux loss over the GLOBAL batch (pmean over data axes).
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], n_experts, dtype=jnp.float32), axis=0)
    aux = n_experts * jnp.sum(me * ce)
    for ax in data_axes:
        aux = jax.lax.pmean(aux, ax)

    # ---- 2. local scatter into per-destination send queues ---------------
    flat_e = idx.reshape(-1)                                # (T_loc·k,)
    tok_id = jnp.repeat(jnp.arange(t), top_k)
    dest = flat_e // e_loc                                  # device owning it
    e_local_idx = flat_e % e_loc
    c_send = max(int(t * top_k / n_ep * capacity_factor), top_k)
    c_send = -(-c_send // 8) * 8
    send, send_e, pos_send, keep = _fill_queue(
        xt[tok_id], dest, jnp.ones_like(dest, bool), n_ep, c_send,
        extra=e_local_idx + 1,                              # 0 = empty slot
    )

    # ---- 3. EP all_to_all (the ONLY cross-device traffic) ----------------
    if wire_dtype == "int8":
        recv = quantized_all_to_all(send, ep_axis)
    else:
        recv = jax.lax.all_to_all(send, ep_axis, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, ep_axis, 0, 0, tiled=True)

    # ---- 4. regroup by local expert, grouped GEMMs ------------------------
    rflat = recv.reshape(n_ep * c_send, d)
    reflat = recv_e.reshape(n_ep * c_send)                  # 0=empty, 1..E_loc
    # local regroup at cf=1.0: the send-side capacity factor already absorbs
    # routing imbalance; padding again here just multiplies empty-slot GEMM
    # work (measured +56% expert FLOPs at cf=1.25², §Perf A iter-3).
    c_loc = max(int(n_ep * c_send / e_loc), 8)
    c_loc = min(-(-c_loc // 8) * 8, n_ep * c_send)
    buf, _, pos_loc, keep_loc = _fill_queue(
        rflat, jnp.maximum(reflat - 1, 0), reflat > 0, e_loc, c_loc
    )

    h = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    out_e = jnp.einsum("ecf,efd->ecd", act(g) * h, params["w_out"])

    # ---- 5. return trip + combine -----------------------------------------
    back = jnp.zeros_like(rflat)
    safe_e = jnp.where(keep_loc, jnp.maximum(reflat - 1, 0), 0)
    gathered = out_e[safe_e, jnp.where(keep_loc, pos_loc, 0)]
    back = jnp.where(keep_loc[:, None], gathered, 0).reshape(n_ep, c_send, d)
    if wire_dtype == "int8":
        res = quantized_all_to_all(back, ep_axis)     # (n_ep, C_send, D)
    else:
        res = jax.lax.all_to_all(back, ep_axis, 0, 0, tiled=True)

    per_copy = res[jnp.where(keep, dest, 0), jnp.where(keep, pos_send, 0)]
    per_copy = jnp.where(keep[:, None], per_copy, 0)
    combined = jnp.zeros((t, d), x.dtype).at[tok_id].add(
        (per_copy * gates.reshape(-1)[:, None]).astype(x.dtype)
    )

    if "shared" in params:
        sp = params["shared"]
        hs = act(xt @ sp["w_gate"]) * (xt @ sp["w_in"])
        combined = combined + hs @ sp["w_out"]

    return combined.reshape(b, s, d), aux.astype(jnp.float32)
