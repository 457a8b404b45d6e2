"""Server folds in a closed loop: each fold hands every upload of its cohort
to ``Aggregator.add`` with its |D_k|, calls ``finalize`` and serializes the
new global with ``broadcast_blob`` — the server half of a T-FedAvg round
(Algorithm 2), with the program's own defaults (``FedConfig()``).

Set-up draws the global model and a pool of distinct client payloads, each
the global plus N(0, sigma²) noise pushed through the program's client
encode (``init_wq_tree``, ``client_update_payload``, ``compress_pytree``,
``encode_update``). Between folds the harness holds what a mean-rule server
holds: the last broadcast blob.

Traffic keys: ``pool`` distinct uploads; ``per_fold`` uploads in a fold;
``draw``: "rotate" (every upload, the order shifted by one each fold and the
weights kept by position, so each upload's weight changes) or "sample"
(``per_fold`` of the pool without replacement); ``weights`` (see
``gen.silo_weights``); ``sigma``; ``warmup`` folds; ``check`` folds compared,
drawn from the first ``check_within`` of the window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import fttq
import gen
import harness
import wire


class State:
    pass


def setup(ctx) -> State:
    from repro.comm.wire import encode_update
    from repro.core import fttq as program_fttq
    from repro.core.compression import compress_pytree
    from repro.core.tfedavg import client_update_payload
    from repro.fed import FedConfig
    from repro.fed.simulation import resolve_compression, resolve_rule

    tr = ctx.traffic
    st = State()
    st.fcfg = FedConfig()
    st.rule, st.trim = resolve_rule(st.fcfg)
    spec = resolve_compression(st.fcfg).upstream
    shapes = ctx.model.program_shapes(ctx.config)
    st.paths = gen.tree_paths(shapes)
    st.shapes = [tuple(int(d) for d in l.shape) for l in jax.tree_util.tree_leaves(shapes)]
    st.scales = ctx.model.init_scales(ctx.config, st.paths, st.shapes)
    st.gkey = gen.key(ctx.seed, 10)
    g = gen.params(shapes, st.scales, st.gkey)
    init_wq = jax.jit(lambda p: program_fttq.init_wq_tree(p, st.fcfg.fttq))
    st.pool = []
    for k in range(tr["pool"]):
        p = gen.perturbed(g, gen.key(ctx.seed, 11, k), tr["sigma"])
        wq = init_wq(p)
        pay = client_update_payload(p, wq, st.fcfg.fttq, fused=spec.fused_encode)
        pay, _ = compress_pytree(pay, spec)
        st.pool.append(encode_update(pay))
        del p, wq, pay
    del g
    st.weights = gen.silo_weights(ctx.seed, tr["pool"], tr["weights"])
    st.n_ternary = sum(int(np.prod(s)) for p, s in zip(st.paths, st.shapes)
                       if ctx.model.quantizable(p, s))
    st.n_raw = sum(int(np.prod(s)) for s in st.shapes) - st.n_ternary
    return st


def plan(ctx, st: State, f: int) -> tuple[list[int], list[float]]:
    tr = ctx.traffic
    n = tr["pool"]
    if tr["draw"] == "rotate":
        return [(i + f) % n for i in range(n)], [float(w) for w in st.weights]
    rng = gen.rng(ctx.seed, 4, f)
    idx = [int(i) for i in rng.choice(n, tr["per_fold"], replace=False)]
    return idx, [float(st.weights[i]) for i in idx]


def fold(ctx, st: State, f: int) -> bytes:
    from repro.fed import Aggregator
    from repro.fed.simulation import broadcast_blob

    idx, w = plan(ctx, st, f)
    with ctx.span("bench.ingest"):
        agg = Aggregator(chunk_c=st.fcfg.agg_chunk_c, rule=st.rule, trim_frac=st.trim)
        for i, wt in zip(idx, w):
            agg.add(st.pool[i], weight=wt)
        new = agg.finalize()
        jax.block_until_ready(new)
    with ctx.span("bench.broadcast"):
        blob = broadcast_blob(new, st.fcfg)
    return blob


# ---------------------------------------------------------------------------
# Reference: regenerate every client's leaf from the seed, quantize, fold
# and re-quantize it with the plain FTTQ rules, one leaf at a time.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "scale", "sigma", "quant", "stacked", "dtype"))
def ref_leaf(gkey, ckeys, wts, i, *, shape, scale, sigma, quant, stacked, dtype):
    base = gen.leaf_value(gkey, i, shape, scale)

    def body(c, acc):
        theta = gen.perturb_leaf(base, ckeys[c], i, sigma).astype(dtype)
        if quant:
            codes, wq = fttq.client(theta, stacked)
            if stacked:
                wq = wq.reshape((-1,) + (1,) * (len(shape) - 1))
            return acc + wts[c].astype(dtype) * wq * codes
        return acc + wts[c].astype(dtype) * theta

    acc = jax.lax.fori_loop(0, ckeys.shape[0], body, jnp.zeros(shape, dtype))
    a = acc / jnp.sum(wts).astype(dtype)
    if not quant:
        return a.astype(jnp.float32), None, None
    codes, s, sc = fttq.server(a, stacked)
    return codes.astype(jnp.int8), s.astype(jnp.float32), sc.astype(jnp.float32)


@jax.jit
def _ternary_gap(codes, ref_codes, ref_s, scale, ref_scale, tol):
    undecided = jnp.abs(jnp.abs(ref_s) - fttq.SERVER_DELTA) <= tol
    off = jnp.sum((codes != ref_codes) & ~undecided)
    serr = jnp.max(jnp.abs(scale.reshape(-1) - ref_scale.reshape(-1)) / jnp.abs(ref_scale.reshape(-1)))
    return off, serr


@jax.jit
def _raw_gap(x, ref):
    return jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref))


def reference(ctx, st: State, f: int, dtype=jnp.float32, cohort=None) -> dict:
    """path → ("ternary", codes, θ_s, scale) or ("raw", array) for fold f
    (of its first ``cohort`` uploads only, where given)."""
    idx, w = plan(ctx, st, f)
    idx, w = idx[:cohort], w[:cohort]
    ckeys = jnp.stack([gen.key(ctx.seed, 11, k) for k in idx])
    wts = jnp.asarray(w, jnp.float32)
    out = {}
    for i, (p, s, sc) in enumerate(zip(st.paths, st.shapes, st.scales)):
        q = ctx.model.quantizable(p, s)
        a, ss, scale = ref_leaf(st.gkey, ckeys, wts, i, shape=s, scale=sc,
                                sigma=ctx.traffic["sigma"], quant=q,
                                stacked=q and len(s) >= 3, dtype=dtype)
        out[p] = ("ternary", a, ss, scale) if q else ("raw", a)
    return out


def program_answer(blob: bytes) -> dict:
    """The broadcast read back by the harness's own wire reader, on the device."""
    out = {}
    for p, rec in wire.read_update(blob).items():
        if rec[0] == "ternary":
            out[p] = ("ternary", wire.unpack_codes(rec[1], rec[2]), jnp.asarray(rec[3]))
        else:
            out[p] = ("raw", jnp.asarray(rec[1]))
    return out


def compare(got: dict, ref: dict, tol: float) -> dict:
    """codes_off: codes that differ where the reference's θ_s lies more than
    ``tol`` from Δ; scale_err: widest relative gap of a broadcast scale;
    raw_err: widest gap of an f32 leaf over its largest magnitude;
    records_off: records missing, extra or of another kind."""
    structure = sum(1 for p in set(got) | set(ref)
                    if p not in got or p not in ref or got[p][0] != ref[p][0])
    off, serr, rerr = 0, 0.0, 0.0
    for p, r in ref.items():
        if p not in got or got[p][0] != r[0]:
            continue
        g = got[p]
        if r[0] == "ternary":
            o, e = _ternary_gap(g[1].astype(jnp.int8), r[1], r[2], g[2], r[3], tol)
            off, serr = off + int(o), max(serr, float(e))
        else:
            rerr = max(rerr, float(_raw_gap(g[1].astype(jnp.float32), r[1])))
    return {"records_off": float(structure), "codes_off": float(off),
            "scale_err": serr, "raw_err": rerr}


def run(ctx) -> dict:
    limits = harness.limits(ctx.cell)
    st = setup(ctx)
    tr = ctx.traffic
    kept = ctx.closed_loop(lambda f: fold(ctx, st, f), warmup=tr["warmup"], check=tr["check"],
                           check_within=tr["check_within"], rng=gen.rng(ctx.seed, 5),
                           counter="folds")
    ctx.read_memory()
    folds = int(ctx.counters["folds"])
    ctx.facts = {"folds": folds, "uploads_per_fold": tr["per_fold"],
                 "n_ternary": st.n_ternary, "n_raw": st.n_raw}
    st.pool = None
    worst: dict = {}
    for f, blob in sorted(kept.items()):
        got = compare(program_answer(blob), reference(ctx, st, f), limits["tol"])
        worst = {k: max(v, worst.get(k, 0.0)) for k, v in got.items()}
    compared = {k: {"value": v, "limit": limits[k]} for k, v in worst.items()}
    return {"attempted": folds, "failed": 0, "compared": compared,
            "end_to_end": {"fold_s": ctx.window_s / folds}}


def readings(ctx) -> dict:
    """The numbers compared, for one seed, of the program's first fold, of
    the control (the reference at bf16 in the program's place), and of two
    faults planted in the reference: half of the cohort left out of the
    mean, and the previous fold's answer returned unchanged."""
    st = setup(ctx)
    got = program_answer(fold(ctx, st, 0))
    st.pool = None
    ref = reference(ctx, st, 0)
    tol = harness.limits(ctx.cell)["tol"]

    def as_answer(r):
        return {p: (x[0], x[1], x[3]) if x[0] == "ternary" else x for p, x in r.items()}

    out = {"program": compare(got, ref, tol),
           "control": compare(as_answer(reference(ctx, st, 0, jnp.bfloat16)), ref, tol),
           "unchanged": compare(as_answer(reference(ctx, st, -1)), ref, tol),
           "half": compare(as_answer(reference(
               ctx, st, 0, cohort=ctx.traffic["per_fold"] // 2)), ref, tol)}
    return out
