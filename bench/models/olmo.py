"""OLMo-1B (arXiv:2402.00838; huggingface.co/allenai/OLMo-1B) as the
program runs it, and its plain reference.

The reference is the decoder written out in ``jax.numpy`` at f32 with
``highest`` matmul precision, one layer at a time: non-parametric
LayerNorm, rotary attention (rotate-half on the two halves of each head),
a SwiGLU MLP, and the head tied to the embedding. Departures from the
published model, all of them the program's: the LayerNorm epsilon is 1e-6
(OLMo uses 1e-5), and the vocabulary is the padded 50,304 rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def program_config(cfg: dict):
    """The program's ModelConfig for this file, refusing any size that the
    two disagree on."""
    from repro.configs import get_config, get_reduced

    mc = (get_reduced if cfg.get("program_reduced") else get_config)(cfg["program_arch"])
    want = {"d_model": cfg["hidden_size"], "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"], "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"], "rope_theta": cfg["rope_theta"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} differs from {cfg['program_arch']} file {want}")
    return mc


def program_shapes(cfg: dict):
    from repro.models.transformer import init_params

    mc = program_config(cfg)
    return jax.eval_shape(lambda k: init_params(mc, k), jax.random.PRNGKey(0))


def init_scales(cfg: dict, paths: list[str], shapes: list[tuple]) -> list:
    """Harness weights: the embedding N(0, 0.02²), each matrix
    N(0, 1/fan_in) with fan_in its contraction size."""
    return [0.02 if "embed" in p else float(1.0 / np.sqrt(s[-2]))
            for p, s in zip(paths, shapes)]


def quantizable(path: str, shape: tuple) -> bool:
    """Weight matrices are ternary; the tied embedding stays f32."""
    return len(shape) >= 2 and "embed" not in path


# ---------------------------------------------------------------------------
# Plain reference forward.
# ---------------------------------------------------------------------------


def _ln(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _rope(x, pos, theta):
    """x (B, S, H, D) rotated by positions pos (S,): halves (x1, x2) →
    (x1 cos − x2 sin, x2 cos + x1 sin), frequency theta^(−2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fp8(x):
    """Round a matmul operand to float8 e4m3 with one scale per tensor (its
    largest magnitude onto 448), and back: the control's operand precision."""
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _ops(*xs, low: bool):
    return [fp8(x) for x in xs] if low else list(xs)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "low"))
def layer(x, w, *, heads: int, theta: float, low: bool = False):
    """One decoder layer over the whole sequence, causal. ``w`` maps
    wq, wk, wv, wo, w_in, w_gate, w_out to dense matrices. ``low`` rounds
    every matmul operand to float8 (the control)."""
    b, s, d = x.shape
    hd = d // heads

    def mm(a, m):
        a, m = _ops(a, m, low=low)
        return a @ m

    h = _ln(x)
    q = mm(h, w["wq"]).reshape(b, s, heads, hd)
    k = mm(h, w["wk"]).reshape(b, s, heads, hd)
    v = mm(h, w["wv"]).reshape(b, s, heads, hd)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    att = jnp.einsum("bqhd,bkhd->bhqk", *_ops(q, k, low=low)) / jnp.sqrt(jnp.float32(hd))
    att = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], att, -jnp.inf)
    att = jax.nn.softmax(att, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", *_ops(att, v, low=low)).reshape(b, s, d)
    x = x + mm(o, w["wo"])
    h = _ln(x)
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_in"]), w["w_out"])


@functools.partial(jax.jit, static_argnames=("low",))
def head(x, table, low: bool = False):
    a, t = _ops(_ln(x), table, low=low)
    return a @ t.T


@jax.jit
def embed(table, tokens):
    return table[tokens]
