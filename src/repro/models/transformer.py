"""Unified scan-over-layers LM covering every assigned architecture family.

Families:
  dense  — granite-20b, gemma3-4b (5:1 local:global sliding window),
           olmo-1b (non-parametric LN), yi-9b
  moe    — qwen3-moe-30b-a3b (128e top-8), deepseek-moe-16b (2 shared + 64 top-6)
  ssm    — mamba2-370m (SSD)
  hybrid — zamba2-1.2b (Mamba2 backbone + ONE shared attention block applied
           every `attn_every` layers, weights shared, per-application KV cache)
  vlm    — llama-3.2-vision-11b (cross-attn every 5th layer over patch embeds)
  audio  — hubert-xlarge (encoder-only; frontend is a stub — inputs are
           precomputed frame embeddings per the assignment)

Everything is a pure function of (cfg, params, inputs); layers are stacked on
a leading axis and driven by lax.scan so compile time/HLO size is O(1) in
depth. Heterogeneous structure inside the scan (global-vs-local window,
cross-attn layers, shared attn blocks) is expressed with per-layer scalar
scan inputs + lax.cond, NOT python branching, so one traced body serves all
layers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import mamba2 as mb
from repro.models.attention import attention, init_attn
from repro.models.common import apply_norm, dense_init, embed_init, matmul
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe

Pytree = Any
BIG_WINDOW = 1 << 30


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    norm: str = "rmsnorm"            # rmsnorm|layernorm|nonparam
    activation: str = "silu"
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True              # False → encoder-only
    tie_embeddings: bool = False
    # sliding window (gemma3)
    sliding_window: int = 0          # 0 = all-global
    global_every: int = 0            # every Nth layer is global
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    # ssm / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_heads: int = 0
    conv_width: int = 4
    ssm_chunk: int = 128
    attn_every: int = 0              # hybrid: shared attn before every Nth layer
    # vlm
    cross_every: int = 0
    n_patches: int = 0
    # training
    aux_loss_coef: float = 0.01
    remat: str = "none"              # none|full|dots
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    # distribution: mesh axes that carry the batch dim of activations.
    # Empty = no sharding constraints (single-device tests). Set by the
    # launchers; forward() pins activations to P(batch_axes, UNCONSTRAINED…)
    # so reshapes (microbatching, loss flattening) cannot silently
    # replicate the batch (GSPMD otherwise loses the sharding).
    mesh_batch_axes: tuple = ()
    # mesh axis carrying the expert dim of MoE dispatch buffers (EP).
    mesh_ep_axis: str = ""
    # MoE dispatch implementation: "gspmd" (scatter, simple, XLA lowers the
    # cross-shard scatter to full-buffer all-reduces) or "a2a" (shard_map +
    # all_to_all — moves only the routed token copies; see moe_a2a.py and
    # EXPERIMENTS.md §Perf A for the measured 20×+ collective reduction).
    moe_impl: str = "gspmd"
    # dispatch payload dtype on the wire: "bf16" | "int8" (per-slot scales).
    moe_wire: str = "bf16"

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def n_cross(self) -> int:
        return self.n_layers // self.cross_every if self.cross_every else 0

    @property
    def n_attn_apps(self) -> int:
        if not self.attn_every:
            return 0
        return (self.n_layers + self.attn_every - 1) // self.attn_every

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)


# --------------------------------------------------------------------------
# Per-layer static patterns.
# --------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention lookback window (BIG = global)."""
    w = np.full((cfg.n_layers,), BIG_WINDOW, np.int32)
    if cfg.sliding_window:
        w[:] = cfg.sliding_window
        if cfg.global_every:
            w[cfg.global_every - 1 :: cfg.global_every] = BIG_WINDOW
    return w


def cross_gates(cfg: ModelConfig) -> np.ndarray:
    g = np.zeros((cfg.n_layers,), np.int32)
    if cfg.cross_every:
        g[cfg.cross_every - 1 :: cfg.cross_every] = 1
    return g


def attn_flags(cfg: ModelConfig) -> np.ndarray:
    f = np.zeros((cfg.n_layers,), np.int32)
    if cfg.attn_every:
        f[0 :: cfg.attn_every] = 1
    return f


# --------------------------------------------------------------------------
# Init.
# --------------------------------------------------------------------------


def _init_dense_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "attn": init_attn(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, dtype
        )
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(
            k2, cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
            cfg.n_shared_experts, cfg.shared_d_ff, dtype,
        )
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    if cfg.norm != "nonparam":
        p["attn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["mlp_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _init_mamba_block(key, cfg: ModelConfig, dtype):
    p = {
        "mamba": mb.init_mamba(
            key, cfg.d_model, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_expand,
            cfg.conv_width, dtype,
        )
    }
    if cfg.norm != "nonparam":
        p["norm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _init_cross_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "attn": init_attn(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, dtype
        ),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype),
        "gate_attn": jnp.zeros((), dtype),
        "gate_mlp": jnp.zeros((), dtype),
    }
    if cfg.norm != "nonparam":
        p["attn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["mlp_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Pytree:
    """Build the parameter pytree (stacked layers). eval_shape-safe."""
    dtype = cfg.pdtype()
    keys = jax.random.split(key, 8)
    params: dict = {}
    params["embed"] = {"table": embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype)}

    layer_keys = jax.random.split(keys[1], cfg.n_layers)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        params["blocks"] = jax.vmap(
            lambda k: _init_dense_block(k, cfg, dtype)
        )(layer_keys)
    elif cfg.family in ("ssm", "hybrid"):
        params["blocks"] = jax.vmap(
            lambda k: _init_mamba_block(k, cfg, dtype)
        )(layer_keys)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    if cfg.family == "vlm":
        cross_keys = jax.random.split(keys[2], cfg.n_cross)
        params["cross"] = jax.vmap(
            lambda k: _init_cross_block(k, cfg, dtype)
        )(cross_keys)

    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(keys[3])
        shared = {
            "attn": init_attn(
                k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, dtype,
            ),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype),
        }
        if cfg.norm != "nonparam":
            shared["attn_norm"] = jnp.zeros((cfg.d_model,), dtype)
            shared["mlp_norm"] = jnp.zeros((cfg.d_model,), dtype)
        params["shared_attn"] = shared

    if cfg.norm != "nonparam":
        params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[4], (cfg.d_model, cfg.vocab_size), dtype)
    return params


def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))


# --------------------------------------------------------------------------
# Cache.
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Pytree:
    """Decode cache pytree. Structure depends on the family."""
    dtype = dtype or cfg.cdtype()
    hd = cfg.resolved_head_dim
    l = cfg.n_layers
    cache: dict = {}
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        cache["k"] = jnp.zeros((l, batch, max_seq, cfg.n_kv_heads, hd), dtype)
        cache["v"] = jnp.zeros((l, batch, max_seq, cfg.n_kv_heads, hd), dtype)
    if cfg.family in ("ssm", "hybrid"):
        d_in = mb.d_inner_of(cfg.d_model, cfg.ssm_expand)
        conv_ch = d_in + 2 * cfg.ssm_state
        p = d_in // cfg.ssm_heads
        cache["conv"] = jnp.zeros((l, batch, cfg.conv_width - 1, conv_ch), dtype)
        cache["ssd"] = jnp.zeros(
            (l, batch, cfg.ssm_heads, p, cfg.ssm_state), jnp.float32
        )
    if cfg.family == "hybrid":
        a = cfg.n_attn_apps
        cache["attn_k"] = jnp.zeros((a, batch, max_seq, cfg.n_kv_heads, hd), dtype)
        cache["attn_v"] = jnp.zeros((a, batch, max_seq, cfg.n_kv_heads, hd), dtype)
    return cache


# --------------------------------------------------------------------------
# Layer bodies.
# --------------------------------------------------------------------------


def _attn_kwargs(cfg: ModelConfig):
    return dict(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope,
        causal=cfg.causal,
    )


def _dense_layer(cfg, bp, x, window, kv, pos):
    """One dense/moe/vlm/audio layer. kv = (k,v) slices or None."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(x, bp.get("attn_norm"), cfg.norm)
    attn_out, new_kv = attention(
        bp["attn"], h, window=window, cache=kv, pos=pos, **_attn_kwargs(cfg)
    )
    x = x + attn_out
    h = apply_norm(x, bp.get("mlp_norm"), cfg.norm)
    if cfg.family == "moe":
        if cfg.moe_impl == "a2a" and cfg.mesh_ep_axis:
            mo, aux = _moe_a2a_shardmapped(cfg, bp["moe"], h)
        else:
            mo, aux = moe(
                bp["moe"], h, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, activation=cfg.activation,
                ep_axis=cfg.mesh_ep_axis, batch_axes=cfg.mesh_batch_axes,
            )
        x = x + mo
    else:
        x = x + mlp(bp["mlp"], h, cfg.activation)
    return x, new_kv, aux


def _moe_a2a_shardmapped(cfg, mp, x):
    """Run the all_to_all MoE inside a shard_map manual over
    (batch_axes ∪ {ep_axis}); expert weights enter EP-split, everything
    else replicated (FSDP shards re-gather here — normal per-layer FSDP)."""
    from repro.models.moe_a2a import moe_a2a

    P = jax.sharding.PartitionSpec
    bax = tuple(cfg.mesh_batch_axes)
    ep = cfg.mesh_ep_axis
    x_spec = P(bax if bax else None, None, None)
    pspecs = {
        "router": P(),
        "w_in": P(ep, None, None),
        "w_gate": P(ep, None, None),
        "w_out": P(ep, None, None),
    }
    if "shared" in mp:
        pspecs["shared"] = {k: P() for k in mp["shared"]}

    def fn(xx, pp):
        return moe_a2a(
            pp, xx, top_k=cfg.top_k, n_experts=cfg.n_experts,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            ep_axis=ep, data_axes=bax, wire_dtype=cfg.moe_wire,
        )

    return jax.shard_map(
        fn, in_specs=(x_spec, pspecs), out_specs=(x_spec, P()),
        axis_names=set(bax) | {ep}, check_vma=False,
    )(x, mp)


def _cross_layer(cfg, cp, x, vision):
    h = apply_norm(x, cp.get("attn_norm"), cfg.norm)
    co, _ = attention(cp["attn"], h, kv_source=vision, **_attn_kwargs(cfg))
    x = x + jnp.tanh(cp["gate_attn"]) * co
    h = apply_norm(x, cp.get("mlp_norm"), cfg.norm)
    x = x + jnp.tanh(cp["gate_mlp"]) * mlp(cp["mlp"], h, cfg.activation)
    return x


def _shared_attn_layer(cfg, sp, x, kv, pos):
    h = apply_norm(x, sp.get("attn_norm"), cfg.norm)
    ao, new_kv = attention(sp["attn"], h, cache=kv, pos=pos, **_attn_kwargs(cfg))
    x = x + ao
    h = apply_norm(x, sp.get("mlp_norm"), cfg.norm)
    x = x + mlp(sp["mlp"], h, cfg.activation)
    return x, new_kv


def _mamba_layer(cfg, bp, x, states):
    h = apply_norm(x, bp.get("norm"), cfg.norm)
    mo, new_states = mb.mamba_block(
        bp["mamba"], h,
        n_heads=cfg.ssm_heads, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
        conv_width=cfg.conv_width, chunk=cfg.ssm_chunk, cache=states,
    )
    return x + mo, new_states


def constrain_batch(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Pin dim-0 of an activation to the batch mesh axes (no-op when
    cfg.mesh_batch_axes is empty)."""
    if not cfg.mesh_batch_axes or x.ndim < 2:
        return x
    u = jax.sharding.PartitionSpec.UNCONSTRAINED
    spec = jax.sharding.PartitionSpec(
        tuple(cfg.mesh_batch_axes), *([u] * (x.ndim - 1))
    )
    return jax.lax.with_sharding_constraint(x, spec)


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------
# Forward.
# --------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Pytree,
    tokens: jax.Array | None = None,
    *,
    embeds: jax.Array | None = None,
    vision_embeds: jax.Array | None = None,
    cache: Pytree | None = None,
    pos: jax.Array | int = 0,
):
    """Returns (logits f32 (B,S,V), new_cache (or None), aux_loss scalar)."""
    cdt = cfg.cdtype()
    if embeds is not None:
        x = embeds.astype(cdt)
    else:
        x = params["embed"]["table"][tokens].astype(cdt)
    x = constrain_batch(cfg, x)
    use_cache = cache is not None

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        windows = jnp.asarray(layer_windows(cfg))
        gates = jnp.asarray(cross_gates(cfg))
        cross_stack = params.get("cross")
        vis = vision_embeds.astype(cdt) if vision_embeds is not None else None

        def body(carry, xs):
            if use_cache:
                bp, w, g, kc, vc = xs
            else:
                bp, w, g = xs
                kc = vc = None
            x, cross_idx = carry
            x = constrain_batch(cfg, x)
            kv = (kc, vc) if use_cache else None
            x, new_kv, aux = _dense_layer(cfg, bp, x, w, kv, pos)
            if cross_stack is not None:
                def do_cross(x):
                    cp = jax.tree_util.tree_map(
                        lambda t: jax.lax.dynamic_index_in_dim(
                            t, cross_idx, 0, keepdims=False
                        ),
                        cross_stack,
                    )
                    return _cross_layer(cfg, cp, x, vis)
                x = jax.lax.cond(g > 0, do_cross, lambda x: x, x)
                cross_idx = cross_idx + g
            ys = (new_kv[0], new_kv[1], aux) if use_cache else aux
            return (x, cross_idx), ys

        body = _maybe_remat(cfg, body)
        xs = (params["blocks"], windows, gates)
        if use_cache:
            xs = xs + (cache["k"], cache["v"])
        (x, _), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)), xs)
        if use_cache:
            new_k, new_v, aux = ys
            new_cache = {"k": new_k, "v": new_v}
        else:
            aux = ys
            new_cache = None
        aux = jnp.sum(aux)

    elif cfg.family == "ssm":
        def body(carry, xs):
            (x,) = carry
            x = constrain_batch(cfg, x)
            if use_cache:
                bp, conv_c, ssd_c = xs
                states = {"conv": conv_c, "ssd": ssd_c}
            else:
                (bp,) = xs
                states = None
            x, new_states = _mamba_layer(cfg, bp, x, states)
            # only emit state ys when serving: stacking 48 layers of SSD
            # states during training wastes GBs of scan-output memory.
            ys = (new_states["conv"], new_states["ssd"]) if use_cache else None
            return (x,), ys

        body = _maybe_remat(cfg, body)
        xs = (params["blocks"], cache["conv"], cache["ssd"]) if use_cache else (params["blocks"],)
        (x,), ys = jax.lax.scan(body, (x,), xs)
        new_cache = {"conv": ys[0], "ssd": ys[1]} if use_cache else None
        aux = jnp.zeros((), jnp.float32)

    elif cfg.family == "hybrid":
        flags = jnp.asarray(attn_flags(cfg))
        shared = params["shared_attn"]

        def body(carry, xs):
            if use_cache:
                bp, flag, conv_c, ssd_c = xs
                states = {"conv": conv_c, "ssd": ssd_c}
                x, app_idx, ak, av = carry
            else:
                bp, flag = xs
                states = None
                x, app_idx = carry[0], carry[1]
                ak = av = None

            def do_attn(operands):
                x, ak, av = operands
                if use_cache:
                    kc = jax.lax.dynamic_index_in_dim(ak, app_idx, 0, keepdims=False)
                    vc = jax.lax.dynamic_index_in_dim(av, app_idx, 0, keepdims=False)
                    x, new_kv = _shared_attn_layer(cfg, shared, x, (kc, vc), pos)
                    ak = jax.lax.dynamic_update_index_in_dim(ak, new_kv[0], app_idx, 0)
                    av = jax.lax.dynamic_update_index_in_dim(av, new_kv[1], app_idx, 0)
                else:
                    x, _ = _shared_attn_layer(cfg, shared, x, None, pos)
                return x, ak, av

            def no_attn(operands):
                return operands

            x = constrain_batch(cfg, x)
            if use_cache:
                x, ak, av = jax.lax.cond(flag > 0, do_attn, no_attn, (x, ak, av))
            else:
                x, _, _ = jax.lax.cond(flag > 0, do_attn, no_attn, (x, None, None))
            app_idx = app_idx + flag
            x, new_states = _mamba_layer(cfg, bp, x, states)
            carry = (x, app_idx, ak, av) if use_cache else (x, app_idx)
            ys = (new_states["conv"], new_states["ssd"]) if use_cache else None
            return carry, ys

        body = _maybe_remat(cfg, body)
        if use_cache:
            xs = (params["blocks"], flags, cache["conv"], cache["ssd"])
            carry0 = (x, jnp.zeros((), jnp.int32), cache["attn_k"], cache["attn_v"])
            (x, _, ak, av), ys = jax.lax.scan(body, carry0, xs)
            new_cache = {"conv": ys[0], "ssd": ys[1], "attn_k": ak, "attn_v": av}
        else:
            xs = (params["blocks"], flags)
            (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)), xs)
            new_cache = None
        aux = jnp.zeros((), jnp.float32)

    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    x = constrain_batch(cfg, x)
    x = apply_norm(x, params.get("final_norm"), cfg.norm)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T.astype(cdt)
    else:
        logits = matmul(x, params["lm_head"])
    # logits stay in compute dtype: upcasting here would make every backward
    # cotangent f32 (2× activation-grad bandwidth + 2× TP all-reduce bytes);
    # the loss upcasts inside log_softmax instead.
    return constrain_batch(cfg, logits), new_cache, aux


def decode_step(cfg, params, tokens, cache, pos, *, vision_embeds=None):
    """One-token incremental decode. tokens: (B, 1). pos: int32 fill length."""
    logits, new_cache, _ = forward(
        cfg, params, tokens, vision_embeds=vision_embeds, cache=cache, pos=pos
    )
    return logits, new_cache


def loss_fn(cfg: ModelConfig, params: Pytree, batch: dict):
    """Mean next-token (or per-frame) cross entropy + MoE aux loss."""
    logits, _, aux = forward(
        cfg,
        params,
        batch.get("tokens"),
        embeds=batch.get("embeds"),
        vision_embeds=batch.get("vision_embeds"),
    )
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = -jnp.mean(ll)
    return ce + cfg.aux_loss_coef * aux, {"ce": ce, "aux": aux}
