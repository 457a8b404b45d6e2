"""Synchronous T-FedAvg rounds through the program's entry point,
``repro.fed.run_federated``: the traffic's clients a round, local epochs and
batch size, the program's own server defaults (``FedConfig()``: fused
encode and fan-in, ternary both ways) and Adam on the clients.

One call of ``run_federated`` serves the whole run: its first rounds are
the warm-up, and the window starts at the boundary after them. A round's
end is the ``eval_fn`` hook, which blocks on the committed global model and
stops the call (by an exception of its own) once the window has closed.
The clients are the program's ``ClientDataset`` over the harness's seeded
CIFAR-shaped mixture, split non-IID; a subclass counts the batches they hand
out and records those of two rounds, which are compared: the first, from
the seeded weights (``change_gap``, ``change_gap_median``), and the
window's first, from the global the program held before it
(``window_change_gap``). The reference trains each on the recorded
batches, once the tape of batches is checked against the traffic
(``tape_off``).

Traffic keys: ``samples``, ``classes``, ``noise``, ``hw`` of the mixture;
``clients`` and ``classes_per_client`` of the split; ``per_round`` clients
a round, ``epochs`` local epochs and ``batch`` rows a batch; ``lr`` of
Adam; ``warmup`` rounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import fttq
import gen
import harness


class StopWindow(Exception):
    pass


class State:
    pass


def make_clients(ctx):
    from repro.data.federated import ClientDataset

    tr = ctx.traffic

    class Client(ClientDataset):
        tape: list | None = None      # (client id, x, y) of each batch, when set
        batches_out = 0

        def batches(self, batch_size, rng, epochs=1):
            # a span over the client's local training (from its first batch
            # to its last), and one over the drawing of each batch
            with ctx.span("bench.client"):
                it = super().batches(batch_size, rng, epochs)
                while True:
                    with ctx.span("bench.batch"):
                        xb, yb = next(it, (None, None))
                    if xb is None:
                        return
                    Client.batches_out += 1
                    if Client.tape is not None:
                        Client.tape.append((self.client_id, xb, yb))
                    yield xb, yb

    x, y = gen.cifar_mixture(ctx.seed, tr["samples"], tr["classes"], tuple(tr["hw"]), tr["noise"])
    shards = gen.noniid_shards(y, tr["clients"], tr["classes_per_client"], ctx.seed)
    return Client, [Client(x[s], y[s], k) for k, s in enumerate(shards)], (x, y, shards)


def setup(ctx) -> State:
    from repro.fed import FedConfig
    from repro.optim import adam

    tr = ctx.traffic
    st = State()
    st.fcfg = FedConfig(n_clients=tr["clients"], participation=tr["per_round"] / tr["clients"],
                        local_epochs=tr["epochs"], batch_size=tr["batch"],
                        rounds=1 << 30, seed=ctx.seed & (2**63 - 1))
    st.Client, st.clients, st.data = make_clients(ctx)
    st.sizes = [len(c) for c in st.clients]
    shapes = ctx.model.program_shapes(ctx.config)
    st.paths = gen.tree_paths(shapes)
    st.shapes = [tuple(int(d) for d in l.shape) for l in jax.tree_util.tree_leaves(shapes)]
    st.scales = ctx.model.init_scales(ctx.config, st.paths, st.shapes)
    st.params0 = gen.params(shapes, st.scales, gen.key(ctx.seed, 20))
    st.opt = adam(ctx.traffic["lr"])
    return st


def drive(ctx, st: State, compared_only: bool = False) -> None:
    """Run the program's rounds: warm-up, then the window; or, with
    ``compared_only`` (for the readings), stop after the compared rounds.
    Keeps, for each compared round, the global before and after it and the
    batches it drew."""
    from repro.fed import run_federated

    warm = ctx.traffic["warmup"]
    st.rounds = (1, warm + 1)
    st.g_in, st.g_out, st.tapes, st.refs = {1: st.params0}, {}, {}, {}
    seen = {"r": 0, "span": None}
    st.Client.tape = []

    def eval_fn(p):
        jax.block_until_ready(p)
        if seen["span"] is not None:
            seen["span"].__exit__(None, None, None)
        seen["r"] += 1
        r = seen["r"]
        if r in st.rounds:
            st.g_out[r], st.tapes[r], st.Client.tape = p, st.Client.tape, None
            if compared_only and r == warm + 1:
                raise StopWindow
        if r + 1 in st.rounds:
            st.g_in[r + 1], st.Client.tape = p, []
        if r == warm:
            if not compared_only:
                ctx.begin_window()
            st.Client.batches_out = 0
        elif r > warm:
            ctx.count("rounds")
            if ctx.window_over():
                ctx.end_window()
                ctx.count("batches", st.Client.batches_out)
                raise StopWindow
        # a span from this round's end to the next one's
        seen["span"] = jax.profiler.TraceAnnotation("bench.round")
        seen["span"].__enter__()
        return 0.0, 0.0

    try:
        run_federated(ctx.model.program_apply(), st.params0, st.clients, st.fcfg,
                      st.opt, eval_fn, eval_every=1)
    except StopWindow:
        pass
    for g in (st.g_in, st.g_out):
        for r in g:
            g[r] = [np.asarray(l) for l in jax.tree_util.tree_leaves(g[r])]


def tape_off(ctx, st: State, tape: list) -> int:
    """Departures of a compared round's batches from the traffic: clients
    other than ``per_round`` distinct ones, a client whose batches are not
    ``epochs`` × ⌊|D_k| / batch⌋ in one run, batches of another size, rows
    that are not rows of the client's own shard (with its labels), and rows
    repeated within an epoch."""
    tr = ctx.traffic
    x, y, shards = st.data
    order = []
    for cid, _, _ in tape:
        if not order or order[-1] != cid:
            order.append(cid)
    off = abs(len(order) - tr["per_round"]) + (len(order) - len(set(order)))
    for cid in set(order):
        rows = {x[i].tobytes(): int(y[i]) for i in shards[cid]}
        batches = [(xb, yb) for c, xb, yb in tape if c == cid]
        per_epoch = len(shards[cid]) // tr["batch"]
        off += abs(len(batches) - tr["epochs"] * per_epoch)
        for e in range(0, len(batches), max(per_epoch, 1)):
            seen = set()
            for xb, yb in batches[e:e + per_epoch]:
                off += int(len(xb) != tr["batch"] or len(yb) != len(xb))
                for xr, yr in zip(xb, yb):
                    k = np.asarray(xr, np.float32).tobytes()
                    off += int(rows.get(k) != int(yr) or k in seen)
                    seen.add(k)
    return off


# ---------------------------------------------------------------------------
# Reference: a compared round in plain jax.numpy at f32, highest precision.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _ste(stacked: bool):
    """θ_t = w_q · I(θ) with the straight-through gradient of Alg. 1:
    ∂/∂θ = g · (w_q where I ≠ 0, else 1), ∂/∂w_q = Σ g · I."""
    @jax.custom_vjp
    def q(theta, wq):
        codes, _ = fttq.client(theta, stacked)
        return wq * codes

    def fwd(theta, wq):
        codes, _ = fttq.client(theta, stacked)
        return wq * codes, (codes, wq)

    def bwd(res, g):
        codes, wq = res
        axes = tuple(range(1, g.ndim)) if stacked else None
        gwq = jnp.sum(g * codes, axis=axes, keepdims=stacked).reshape(wq.shape)
        return g * jnp.where(codes != 0, wq, jnp.ones_like(wq)), gwq

    q.defvjp(fwd, bwd)
    return q


@functools.lru_cache(maxsize=None)
def make_reference_client(model, paths, shapes, lr: float, groups: int, dtype,
                          half_batch: bool = False):
    quant = [model.quantizable(p, s) for p, s in zip(paths, shapes)]
    stacked = [q and len(s) >= 3 for q, s in zip(quant, shapes)]

    def unflat(leaves):
        tree: dict = {}
        for p, l in zip(paths, leaves):
            node = tree
            *head, last = p.split("/")
            for h in head:
                node = node.setdefault(h, {})
            node[last] = l
        return tree

    def loss(theta, wq, xb, yb):
        q = [(_ste(s)(t, w) if w is not None else t) for t, w, s in zip(theta, wq, stacked)]
        logits = model.forward(unflat(q), xb, groups=groups, dtype=dtype).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], -1))

    def step(carry, batch):
        theta, wq, m, v, t = carry
        xb, yb = batch
        if half_batch:      # a fault: the mean taken over half of the batch
            xb, yb = xb[: xb.shape[0] // 2], yb[: yb.shape[0] // 2]
        (gt, gw) = jax.grad(loss, argnums=(0, 1))(theta, wq, xb, yb)
        t = t + 1
        m = [0.9 * a + 0.1 * g.astype(jnp.float32) for a, g in zip(m, gt)]
        v = [0.999 * a + 0.001 * jnp.square(g.astype(jnp.float32)) for a, g in zip(v, gt)]
        bc1, bc2 = 1 - 0.9 ** t, 1 - 0.999 ** t
        theta = [(p.astype(jnp.float32) - lr * (a / bc1) / (jnp.sqrt(b / bc2) + 1e-8)).astype(dtype)
                 for p, a, b in zip(theta, m, v)]
        wq = [None if w is None else (w - 0.05 * g / p.size).astype(dtype)
              for w, g, p in zip(wq, gw, theta)]
        gnorm = jnp.stack([jnp.linalg.norm(g.astype(jnp.float32)) for g in gt])
        return (theta, wq, m, v, t), gnorm

    @jax.jit
    def client(start, xs, ys):
        """E local epochs from the decoded broadcast; returns the dequantized
        upload (w_q · I per segment, raw leaves as trained) and the gradient
        norm of each leaf at the first step."""
        theta = [s.astype(dtype) for s in start]
        wq = []
        for t, q, s in zip(theta, quant, stacked):
            if not q:
                wq.append(None)
                continue
            _, w = fttq.client(t, s)
            wq.append(w.reshape((-1,) + (1,) * (t.ndim - 1)) if s else w)
        zeros = [jnp.zeros(t.shape, jnp.float32) for t in theta]
        (theta, wq, _, _, _), gn = jax.lax.scan(
            step, (theta, wq, zeros, zeros, jnp.zeros((), jnp.float32)), (xs, ys))
        up = []
        for t, w, q, s in zip(theta, wq, quant, stacked):
            if q:
                codes, _ = fttq.client(t, s)
                up.append((w * codes).astype(jnp.float32))
            else:
                up.append(t.astype(jnp.float32))
        return up, gn[0]

    return client


def reference_round(ctx, st: State, r: int, dtype=jnp.float32, cohort=None, half_batch=False):
    """The global model after compared round ``r``, from the broadcast of
    the global the program held before it and the recorded batches of each
    client, and each leaf's gradient norm at the first step."""
    paths, shapes, model = st.paths, st.shapes, ctx.model
    tape = st.tapes[r]
    start = []
    for p, s, l in zip(paths, shapes, st.g_in[r]):
        if model.quantizable(p, s):
            codes, _, scale = fttq.server(jnp.asarray(l), len(s) >= 3)
            start.append(codes * (scale.reshape((-1,) + (1,) * (len(s) - 1)) if len(s) >= 3 else scale))
        else:
            start.append(jnp.asarray(l))
    order = []
    for cid, _, _ in tape:
        if not order or order[-1] != cid:
            order.append(cid)
    if cohort is not None:
        order = order[:cohort]
    client = make_reference_client(model, tuple(paths), tuple(shapes), ctx.traffic["lr"],
                                   ctx.config["groupnorm_groups"], dtype, half_batch)
    acc, total, gn0 = None, 0.0, None
    with jax.default_matmul_precision("highest"):
        for cid in order:
            xs = jnp.asarray(np.stack([x for c, x, _ in tape if c == cid]))
            ys = jnp.asarray(np.stack([y for c, _, y in tape if c == cid]))
            up, gn = client(start, xs, ys)
            n = float(st.sizes[cid])
            acc = [n * u for u in up] if acc is None else [a + n * u for a, u in zip(acc, up)]
            total += n
            gn0 = gn if gn0 is None else gn0
    return [np.asarray(a / total) for a in acc], np.asarray(gn0), [np.asarray(s) for s in start]


def norm_gaps(prog, ref, base, gnorm0) -> np.ndarray:
    """Each leaf's gap between the program's and the reference's norm of
    its move from ``base``, over the larger of that leaf's reference norm
    and the median leaf's. Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out: they move by round-off
    alone."""
    dp = np.array([np.linalg.norm(np.asarray(p, np.float64) - b) for p, b in zip(prog, base)])
    dr = np.array([np.linalg.norm(np.asarray(r, np.float64) - b) for r, b in zip(ref, base)])
    keep = gnorm0 >= 1e-3 * np.median(gnorm0)
    med = np.median(dr[keep])
    return np.abs(dp - dr)[keep] / np.maximum(dr[keep], med)


def compare(prog, ref, start, gnorm0, prefix: str = "") -> dict:
    """A round's move from the broadcast it started from, by its worst leaf
    and by its median leaf. (The clients' rule re-quantizes the ternary
    start to itself, so the move is what training and the fold added.)"""
    gaps = norm_gaps(prog, ref, start, gnorm0)
    return {prefix + "change_gap": float(gaps.max()),
            prefix + "change_gap_median": float(np.median(gaps))}


def check(ctx, st: State, got: dict | None = None, **fault) -> dict:
    """The numbers of both compared rounds: the first (``change_gap``...)
    and the window's first (``window_change_gap``...), with ``tape_off``
    over both tapes. ``got`` maps a round to the global to judge (by
    default the program's); ``fault`` is planted in the reference put in
    the program's place: then the reference's output is judged against
    the reference at f32."""
    out = {"tape_off": float(sum(tape_off(ctx, st, st.tapes[r]) for r in st.rounds))}
    for r, prefix in zip(st.rounds, ("", "window_")):
        if r not in st.refs:
            st.refs[r] = reference_round(ctx, st, r)
        ref, gn0, start = st.refs[r]
        if fault:
            prog = reference_round(ctx, st, r, **fault)[0]
        else:
            prog = (got or st.g_out)[r]
        out.update(compare(prog, ref, start, gn0, prefix))
    return out


def run(ctx) -> dict:
    limits = harness.limits(ctx.cell)
    st = setup(ctx)
    drive(ctx, st)
    ctx.read_memory()
    rounds = int(ctx.counters["rounds"])
    ctx.facts = {"rounds": rounds, "batches": ctx.counters["batches"],
                 "batch_size": st.fcfg.batch_size}
    st.clients = None
    got = check(ctx, st)
    compared = {k: {"value": v, "limit": limits[k]} for k, v in got.items() if k in limits}
    return {"attempted": rounds, "failed": 0, "compared": compared,
            "end_to_end": {"round_s": ctx.window_s / rounds}}


def readings(ctx) -> dict:
    """The numbers of both compared rounds, for one seed, of the program,
    of the control (the reference at bf16 in the program's place), and of
    three faults: half of each QAT batch and half of the cohort left out of
    the mean (planted in the reference), and a round that returns the
    global it started from unchanged."""
    st = setup(ctx)
    drive(ctx, st, compared_only=True)
    st.clients = None
    cohort = len({c for c, _, _ in st.tapes[1]})
    return {"program": check(ctx, st),
            "control": check(ctx, st, dtype=jnp.bfloat16),
            "half": check(ctx, st, cohort=cohort // 2),
            "half_batch": check(ctx, st, half_batch=True),
            "unchanged": check(ctx, st, got=st.g_in)}
