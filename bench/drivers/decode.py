"""Greedy decode of the packed ternary deploy artifact in a closed loop of
``repro.launch.serve.generate`` calls (prefill of the prompts into a KV
cache, then one token a step), each call a batch of requests.

Set-up draws the dense f32 weights from the seed, builds the edge artifact
with ``ternary_deploy(packed=True)`` (FTTQ encode, the wire, and the repack
into the kernel's 2-bit layout) and drops the dense copy. Prompts are drawn
uniformly from the vocabulary, a fresh batch for every call.

Traffic keys: ``batch`` requests a call, ``prompt`` and ``gen`` tokens,
``warmup`` calls, ``check`` calls whose requests the reference reads again,
drawn from the first ``check_within`` calls of the window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import fttq
import gen
import harness


class State:
    pass


def prompts(ctx, c: int) -> jax.Array:
    tr = ctx.traffic
    return jax.random.randint(gen.key(ctx.seed, 30, c), (tr["batch"], tr["prompt"]), 0,
                              ctx.config["vocab_size"], jnp.int32)


def setup(ctx) -> State:
    from repro.core import FTTQConfig
    from repro.launch.serve import ternary_deploy

    st = State()
    st.mc = ctx.model.program_config(ctx.config)
    shapes = ctx.model.program_shapes(ctx.config)
    st.paths = gen.tree_paths(shapes)
    st.shapes = [tuple(int(d) for d in l.shape) for l in jax.tree_util.tree_leaves(shapes)]
    st.scales = ctx.model.init_scales(ctx.config, st.paths, st.shapes)
    st.wkey = gen.key(ctx.seed, 31)
    dense = gen.params(shapes, st.scales, st.wkey)
    st.served, blob, _, _ = ternary_deploy(dense, FTTQConfig(), packed=True)
    del dense, blob
    return st


def call(ctx, st: State, c: int) -> jax.Array:
    from repro.launch.serve import generate

    with ctx.span("bench.generate"):
        out = generate(st.mc, st.served, prompts(ctx, c), gen=ctx.traffic["gen"])
        out.block_until_ready()
    return out


# ---------------------------------------------------------------------------
# Reference: the dense forward of the dequantized weights over each prompt
# and its served tokens, one layer at a time.
# ---------------------------------------------------------------------------


def _leaf(st, name):
    i = next(i for i, p in enumerate(st.paths) if p.endswith(name))
    return gen.leaf_value(st.wkey, i, st.shapes[i], st.scales[i])


@jax.jit
def _deploy_dense(theta):
    codes, scale = fttq.deploy(theta)
    return codes.astype(jnp.int8), scale


def reference_logits(ctx, st: State, tokens: jax.Array, low: bool = False) -> jax.Array:
    """(B, S, vocab) logits of the plain forward at f32, highest precision
    (for the control, ``low``: every matmul operand rounded to float8)."""
    cfg = ctx.config
    names = ["wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"]
    codes, scales = {}, {}
    for n in names:
        codes[n], scales[n] = _deploy_dense(_leaf(st, "/" + n))
    table = _leaf(st, "embed/table")
    with jax.default_matmul_precision("highest"):
        x = ctx.model.embed(table, tokens)
        for layer in range(cfg["num_hidden_layers"]):
            w = {n: codes[n][layer].astype(jnp.float32) * scales[n] for n in names}
            x = ctx.model.layer(x, w, heads=cfg["num_attention_heads"],
                                theta=float(cfg["rope_theta"]), low=low)
        return ctx.model.head(x, table, low=low)


@functools.partial(jax.jit, static_argnames=("prompt_len",))
def served_gap(ref, seq, prompt_len):
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position."""
    pred = ref[:, prompt_len - 1:-1]
    tok = seq[:, prompt_len:]
    got = jnp.take_along_axis(pred, tok[..., None], -1)[..., 0]
    return jnp.max(jnp.max(pred, -1) - got)


@functools.partial(jax.jit, static_argnames=("prompt_len",))
def control_gap(ref, ctl, prompt_len):
    """The same gap for the token the control ranks first at each position."""
    pred = ref[:, prompt_len - 1:-1]
    pick = jnp.argmax(ctl[:, prompt_len - 1:-1], -1)
    got = jnp.take_along_axis(pred, pick[..., None], -1)[..., 0]
    return jnp.max(jnp.max(pred, -1) - got)


def sequences(ctx, kept) -> list[tuple[int, jax.Array]]:
    """(call, prompt ⧺ served tokens) of each checked call."""
    return [(c, jnp.concatenate([prompts(ctx, c), jnp.asarray(out)], axis=1))
            for c, out in sorted(kept.items())]


def run(ctx) -> dict:
    limits = harness.limits(ctx.cell)
    st = setup(ctx)
    tr = ctx.traffic
    kept = ctx.closed_loop(lambda c: call(ctx, st, c), warmup=tr["warmup"], check=tr["check"],
                           check_within=tr["check_within"], rng=gen.rng(ctx.seed, 32),
                           counter="calls", keep=np.asarray)
    ctx.read_memory()
    calls = int(ctx.counters["calls"])
    ctx.facts = {"calls": calls, "batch": tr["batch"], "prompt": tr["prompt"], "gen": tr["gen"]}
    st.served = None
    gap = 0.0
    for c, seq in sequences(ctx, kept):
        ref = reference_logits(ctx, st, seq)
        gap = max(gap, float(served_gap(ref, seq, tr["prompt"])))
    tokens = calls * tr["batch"] * tr["gen"]
    return {"attempted": calls * tr["batch"], "failed": 0,
            "compared": {"logit_gap": {"value": gap, "limit": limits["logit_gap"]}},
            "end_to_end": {"decode_tokens_per_s": tokens / ctx.window_s}}


def readings(ctx) -> dict:
    """The number compared, for one seed, of the program's first call after
    a warm-up, and of the control: the reference with its matmul operands
    in float8 (the precision below the configuration's bf16 operands), whose
    first-ranked token at each position of the same sequences is read in
    the f32 reference."""
    st = setup(ctx)
    call(ctx, st, -1)
    seq = sequences(ctx, {0: np.asarray(call(ctx, st, 0))})[0][1]
    st.served = None
    p = ctx.traffic["prompt"]
    ref = reference_logits(ctx, st, seq)
    ctl = reference_logits(ctx, st, seq, low=True)
    return {"program": {"logit_gap": float(served_gap(ref, seq, p))},
            "control": {"logit_gap": float(control_gap(ref, ctl, p))}}
