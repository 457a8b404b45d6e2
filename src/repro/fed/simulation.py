"""Round-based federated simulation (paper Algorithm 2 + §II.A protocol).

Each round:
  1. SELECTION      — sample ⌈λN⌉ clients.
  2. CONFIGURATION  — the server SERIALIZES the current global model through
                      ``repro.comm.wire`` (ternary wire for T-FedAvg —
                      downstream compression, §III.B) and broadcasts the
                      buffer; clients DECODE it. Download bytes are
                      ``len(buffer)`` per recipient.
  3. REPORTING      — clients run E local epochs (FTTQ QAT for T-FedAvg),
                      serialize their update, and upload; the server decodes,
                      aggregates |D_k|-weighted and (T-FedAvg) re-quantizes.

Transfer and compute times come from the ``repro.comm.channel`` model, so a
straggler is a client whose download + compute + upload exceeded the round
deadline — an emergent property of bytes ÷ bandwidth, not a coin flip. The
protocol tolerates partial participation by design: a dropped client only
reweights the average, and the fastest client is always kept so no round is
ever lost.

``run_federated`` is the unified entry point: ``cfg.mode`` selects this
synchronous server or the event-driven buffered-asynchronous one in
``fed/async_server.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.comm import Channel, ChannelConfig
from repro.comm.wire import decode_update, encode_update
from repro.core import fttq as fttq_mod
from repro.core.compression import (
    CodecSpec,
    CompressionSpec,
    compress_pytree,
    decompress_pytree,
)
from repro.core.tfedavg import (
    TernaryUpdate,
    client_update_payload,
    server_aggregate,
    server_requantize,
)
from repro.data.federated import ClientDataset
from repro.fed.aggregator import Aggregator
from repro.fed.attackers import AttackConfig, attacker_ids, poison_blob
from repro.fed.availability import (
    AvailabilityConfig,
    draw_participants,
    make_availability,
)
from repro.fed.controller import (
    CompressionController,
    ControllerConfig,
    make_controller,
)
from repro.fed.defense import DefenseConfig, UpdateGate
from repro.fed.hierarchy import EdgeTier, HierarchyConfig
from repro.optim import Optimizer

Pytree = Any


@dataclasses.dataclass
class FedConfig:
    algorithm: str = "tfedavg"          # "fedavg" | "tfedavg"
    mode: str = "sync"                  # "sync" | "async" (buffered, FedBuf-style)
    n_clients: int = 100
    participation: float = 0.1          # λ
    local_epochs: int = 5               # E
    batch_size: int = 64                # B
    rounds: int = 100                   # sync rounds / async aggregations
    fttq: fttq_mod.FTTQConfig = dataclasses.field(default_factory=fttq_mod.FTTQConfig)
    channel: ChannelConfig = dataclasses.field(default_factory=ChannelConfig)
    # per-direction codec selection (None → derived from `algorithm`:
    # tfedavg → symmetric ternary, fedavg → identity). Asymmetric specs —
    # e.g. fp16 residuals upstream only — change the measured byte split.
    compression: CompressionSpec | None = None
    seed: int = 0
    # --- server aggregation ----------------------------------------------
    # True → stream survivor blobs through fed.aggregator.Aggregator (fused
    # packed fan-in kernel, O(chunk) server memory); False → the list-based
    # reference loop (core.tfedavg.server_aggregate).
    fused_aggregation: bool = True
    agg_chunk_c: int = 16               # clients per fused kernel launch
    # --- client/server egress encode -------------------------------------
    # True → quantize→pack through the fused one-pass kernel pipeline
    # (core.encode: byte-identical wire buffers, one HBM read per leaf);
    # False → the pinned per-leaf jnp reference chain.
    fused_encode: bool = True
    # --- async (buffered) server knobs -----------------------------------
    buffer_k: int = 4                   # aggregate every K arrivals
    max_concurrency: int = 0            # in-flight clients (0 → ⌈λN⌉)
    staleness_exponent: float = 0.5     # arrival weight ∝ (1+staleness)^-α
    mixing_rate: float = 1.0            # η: global ← (1-η)·global + η·buffer avg
    # --- scenario layer ---------------------------------------------------
    # who is reachable when (always_on reproduces pre-scenario runs
    # bit-exactly; "diurnal"/"trace" feed both servers' participant draws).
    availability: AvailabilityConfig = dataclasses.field(
        default_factory=AvailabilityConfig
    )
    # hierarchical edge-aggregation tier (n_edges=0 → flat, the historical
    # topology — pre-hierarchy runs reproduce bit-exactly). With edges on,
    # survivors fan into regional edge aggregators that each ship ONE
    # (optionally re-quantized) record to the root, so root ingress bytes
    # scale with the edge count instead of the participant count.
    hierarchy: HierarchyConfig = dataclasses.field(
        default_factory=HierarchyConfig
    )
    # hard staleness cap for async arrivals (0 → no cap). Past the cap an
    # update is dropped ("drop") or extra-discounted ("downweight").
    max_staleness: int = 0
    staleness_policy: str = "drop"
    # adaptive buffer_k: retune K after every mix so the time between
    # aggregations tracks target_mix_latency_s as arrival rates drift
    # (0 → lock the target to the initial K's observed latency).
    adaptive_buffer: bool = False
    target_mix_latency_s: float = 0.0
    # --- Byzantine robustness ---------------------------------------------
    # content defense (None / enabled=False → the legacy ingest path,
    # bit-exact) and seeded attacker injection (None → all clients honest).
    # With the gate on, every arrival is checked against the broadcast tree
    # BEFORE it reaches the aggregator; failures become the third ledger
    # outcome:  shipped == ingested + dropped + quarantined.
    defense: DefenseConfig | None = None
    attack: AttackConfig | None = None
    # --- adaptive compression controller ----------------------------------
    # None / enabled=False → the static upstream codec path, bit-exact with
    # pre-controller runs. Enabled → fed/controller.py selects each
    # client's upload codec per round from measured goodput + update
    # divergence, with per-client error-feedback residual state; telemetry
    # lands in FedResult.telemetry["controller"].
    controller: "ControllerConfig | None" = None


@dataclasses.dataclass
class FedResult:
    accuracy: list
    loss: list
    upload_bytes: int
    download_bytes: int
    rounds_run: int
    participants_per_round: list
    # wall-clock view from the channel model (simulated seconds):
    round_times: list = dataclasses.field(default_factory=list)
    dropped_per_round: list = dataclasses.field(default_factory=list)
    transfer_summary: dict = dataclasses.field(default_factory=dict)
    staleness_per_agg: list = dataclasses.field(default_factory=list)
    # scenario telemetry: staleness histogram, dropped/retransmitted bytes,
    # adaptive buffer_k trajectory, availability kind (see the servers).
    telemetry: dict = dataclasses.field(default_factory=dict)

    @property
    def total_time_s(self) -> float:
        return float(sum(self.round_times))


def _ce_loss(apply_fn, params, xb, yb):
    logits = apply_fn(params, xb)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=-1))


def make_local_steps(apply_fn, optimizer: Optimizer, cfg: FedConfig):
    """jit'd per-batch SGD steps for the FP (FedAvg) and QAT (T-FedAvg) paths."""

    @jax.jit
    def fp_step(params, opt_state, xb, yb):
        loss, grads = jax.value_and_grad(
            lambda p: _ce_loss(apply_fn, p, xb, yb)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, updates
        )
        return params, opt_state, loss

    fcfg = cfg.fttq

    @jax.jit
    def qat_step(params, wq, opt_state, xb, yb):
        def loss_fn(p, w):
            q = fttq_mod.quantize_tree(p, w, fcfg)
            return _ce_loss(apply_fn, q, xb, yb)

        loss, (g_p, g_w) = jax.value_and_grad(loss_fn, argnums=(0, 1))(params, wq)
        updates, opt_state = optimizer.update(g_p, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, updates
        )
        # w_q trains by SGD (paper Alg. 1); its gradient is a SUM over every
        # quantized position of the layer, so normalize per-element to keep
        # the step size layer-size-invariant.

        def upd_wq(w, g, p):
            if w is None:
                return None
            return w - 0.05 * g / float(p.size)

        wq = jax.tree_util.tree_map(
            upd_wq, wq, g_w, params, is_leaf=lambda x: x is None
        )
        return params, wq, opt_state, loss

    return fp_step, qat_step


# --------------------------------------------------------------------------
# Shared protocol pieces (used by both the sync and async servers).
# --------------------------------------------------------------------------


def resolve_rule(cfg: FedConfig) -> tuple[str, float]:
    """The (aggregation rule, trim fraction) every server in this run uses.

    Defense off (the default) pins "mean" — the legacy bit-exact weighted
    average. The robust rules live on the fused ``fed.aggregator`` path;
    the list-based reference loop only knows the mean, so they require
    ``fused_aggregation=True``.
    """
    if cfg.defense is None or not cfg.defense.enabled:
        return "mean", 0.2
    if cfg.defense.rule != "mean" and not cfg.fused_aggregation:
        raise ValueError(
            f"robust rule {cfg.defense.rule!r} requires fused_aggregation=True "
            "(the reference loop only computes the weighted mean)"
        )
    return cfg.defense.rule, cfg.defense.trim_frac


def resolve_compression(cfg: FedConfig) -> CompressionSpec:
    """The run's per-direction codec pair (explicit, or derived from the
    algorithm: T-FedAvg ships ternary both ways, FedAvg ships raw fp32)."""
    if cfg.compression is not None:
        return cfg.compression
    kind = "ternary" if cfg.algorithm == "tfedavg" else "none"
    return CompressionSpec.symmetric(
        kind=kind, fttq=cfg.fttq, fused_encode=cfg.fused_encode
    )


def dequantize_tree(tree: Pytree) -> Pytree:
    """Decode any wire leaves (ternary/downcast/top-k); raw leaves pass."""
    return decompress_pytree(tree)


def broadcast_blob(global_params: Pytree, cfg: FedConfig) -> bytes:
    """Serialize the downstream payload through the downstream codec spec.

    The ternary weights path keeps Algorithm 2's server re-quantization
    (fixed Δ = server_delta); the residual codec then compresses whatever
    leaves are still raw (biases, norms) — that is where the remaining
    downstream bytes live.
    """
    with obs.span("repro.broadcast"):
        dspec = resolve_compression(cfg).downstream
        with obs.span("repro.broadcast.requantize"):
            if dspec.kind == "ternary":
                tree = server_requantize(global_params, dspec.fttq,
                                         fused=dspec.fused_encode)
                tree, _ = compress_pytree(tree, dspec)  # residual codec on raw leaves
            else:
                tree, _ = compress_pytree(global_params, dspec)
        return encode_update(tree)


def receive_broadcast(blob: bytes) -> Pytree:
    """Client side of CONFIGURATION: decode the wire buffer, dequantize.
    Decoded once per broadcast — the result is shared by every recipient of
    the same (immutable) buffer."""
    with obs.span("repro.round.receive"):
        return dequantize_tree(decode_update(blob))


def local_train(
    client: ClientDataset,
    start_params: Pytree,
    cfg: FedConfig,
    optimizer: Optimizer,
    fp_step,
    qat_step,
    rng: np.random.Generator,
) -> tuple[Pytree, Pytree | None]:
    """E local epochs from the decoded broadcast: FTTQ QAT for T-FedAvg,
    plain SGD for FedAvg. Returns (trained params, trained w_q tree — None
    for FedAvg)."""
    params_k = start_params
    qat = cfg.algorithm == "tfedavg"
    with obs.span("repro.client.init", client=client.client_id):
        opt_state = optimizer.init(params_k)
        wq = fttq_mod.init_wq_tree(params_k, cfg.fttq) if qat else None
    for xb, yb in client.batches(cfg.batch_size, rng, cfg.local_epochs):
        with obs.span("repro.client.transfer"):
            xb, yb = jnp.asarray(xb), jnp.asarray(yb)
        with obs.span("repro.client.step"):
            if qat:
                params_k, wq, opt_state, _ = qat_step(params_k, wq, opt_state, xb, yb)
            else:
                params_k, opt_state, _ = fp_step(params_k, opt_state, xb, yb)
        obs.count("client.steps")
    return params_k, wq


def train_client(
    client: ClientDataset,
    start_params: Pytree,
    cfg: FedConfig,
    optimizer: Optimizer,
    fp_step,
    qat_step,
    rng: np.random.Generator,
    *,
    controller: CompressionController | None = None,
    client_id: int = -1,
) -> bytes:
    """One client's round: train locally from the decoded broadcast
    (``receive_broadcast``), serialize the upstream payload through the
    upstream codec spec (QAT ternary weights pass through untouched; the
    residual codec compresses the raw bias/norm leaves). With an adaptive
    ``controller``, the encode instead goes through its per-client rung
    selection + error feedback (``controller.client_payload``); training
    itself is identical either way."""
    params_k, wq = local_train(client, start_params, cfg, optimizer,
                               fp_step, qat_step, rng)
    with obs.span("repro.client.encode"):
        if controller is not None:
            return controller.client_payload(client_id, params_k, wq,
                                             start_params)
        if wq is None:
            payload = params_k
        else:
            # gate on the RESOLVED upstream spec (not cfg.fused_encode directly)
            # so an explicit cfg.compression's fused_encode flag is honored on
            # this path exactly as broadcast_blob honors the downstream one.
            payload = client_update_payload(
                params_k, wq, cfg.fttq,
                fused=resolve_compression(cfg).upstream.fused_encode,
            )
        payload, _ = compress_pytree(payload, resolve_compression(cfg).upstream)
        return encode_update(payload)


# --------------------------------------------------------------------------
# Synchronous server (paper Algorithm 2).
# --------------------------------------------------------------------------


def run_federated_sync(
    apply_fn: Callable,
    global_params: Pytree,
    clients: list[ClientDataset],
    cfg: FedConfig,
    optimizer: Optimizer,
    eval_fn: Callable[[Pytree], tuple[float, float]],
    *,
    eval_every: int = 10,
) -> FedResult:
    rng = np.random.default_rng(cfg.seed)
    fp_step, qat_step = make_local_steps(apply_fn, optimizer, cfg)
    channel = Channel(cfg.channel, len(clients), seed=cfg.seed + 1)
    avail = make_availability(cfg.availability, len(clients), seed=cfg.seed)
    deadline = cfg.channel.deadline_s if cfg.channel.deadline_s > 0 else float("inf")

    up_bytes = 0
    down_bytes = 0
    dropped_blob_bytes = 0     # uploads that arrived past the deadline
    acc_hist, loss_hist, parts_hist = [], [], []
    round_times, dropped_hist = [], []
    n_sel = max(int(np.ceil(cfg.participation * len(clients))), 1)
    t_now = 0.0                # cumulative simulated time (availability clock)
    rule, trim_frac = resolve_rule(cfg)
    # long-lived edge tier (when enabled): per-edge staging buffers, leaf
    # plans and the cumulative byte ledger persist across rounds.
    tier = (EdgeTier(cfg.hierarchy, cfg.fttq, len(clients),
                     fused_encode=cfg.fused_encode,
                     rule=rule, trim_frac=trim_frac)
            if cfg.hierarchy.enabled else None)
    # Byzantine layer: seeded attacker cohort + the content gate. The gate
    # lives across rounds so its cross-client scale history warms up.
    attackers = (attacker_ids(cfg.attack, len(clients))
                 if cfg.attack is not None else frozenset())
    gate = (UpdateGate(cfg.defense, global_params)
            if cfg.defense is not None and cfg.defense.enabled else None)
    gated_bytes = 0            # survivor bytes presented to the gate
    # adaptive compression controller (None → static codec path, bit-exact).
    ctrl = make_controller(cfg)
    if ctrl is not None and rule != "mean":
        raise ValueError(
            "adaptive compression requires aggregation rule 'mean': "
            "mixed-codec rounds have no robust-vote decomposition"
        )
    up_bytes_per_round, down_bytes_per_round = [], []

    for r in range(cfg.rounds):
        with obs.span("repro.round", round=r):
            if ctrl is not None:
                ctrl.note_round(r)
            round_up0 = up_bytes
            # ---- selection (from the clients ONLINE right now) --------------
            wait_s = 0.0
            selected = draw_participants(avail, t_now, n_sel, len(clients), rng)
            while selected.size == 0:   # fleet empty: wait for the next arrival
                t_next = avail.next_change(t_now + wait_s)
                if not np.isfinite(t_next):
                    raise RuntimeError("no client is ever available")
                wait_s = t_next - t_now
                selected = draw_participants(avail, t_next, n_sel,
                                             len(clients), rng)

            # ---- configuration (downstream broadcast, one serialized buffer) -
            blob = broadcast_blob(global_params, cfg)
            down_bytes += len(blob) * len(selected)
            down_bytes_per_round.append(len(blob) * len(selected))
            start_params = receive_broadcast(blob)

            # ---- local training + reporting (upstream) ----------------------
            # Download + compute time are known before training; a client whose
            # link/device alone blows the deadline is dropped WITHOUT paying for
            # local training (the upload could only add time). The fastest
            # pre-time client always trains, so no round is ever lost.
            # The broadcast downloads run SIMULTANEOUSLY and contend for the
            # server NIC (cfg.channel.server_bandwidth_bytes_s).
            sel = [int(k) for k in selected]
            down_times = channel.transfer_concurrent(
                sel, [len(blob)] * len(sel), "down"
            )
            pre = []  # (t_down + t_comp, client_id)
            for t_down, k in zip(down_times, sel):
                t_comp = channel.compute_time(k, len(clients[k]) * cfg.local_epochs)
                pre.append((t_down + t_comp, k))
            pre.sort()

            arrivals = []  # (total_time, client_id, up_blob) — trained clients
            for pt, k in pre:
                if pt > deadline and arrivals:
                    continue            # decidably late; round already safe
                up_blob = train_client(
                    clients[k], start_params, cfg, optimizer, fp_step, qat_step,
                    rng, controller=ctrl, client_id=k,
                )
                if k in attackers:
                    # decode → poison → re-encode: the frame stays wire-valid,
                    # only the content defense can catch it.
                    up_blob = poison_blob(up_blob, cfg.attack, k, round_idx=r)
                t_up = channel.transfer(k, len(up_blob), "up")
                if ctrl is not None:
                    # the same metered view Channel.log records (TransferEvent):
                    # payload bytes over seconds including retransmissions.
                    ctrl.observe_upload(k, len(up_blob), t_up)
                arrivals.append((pt + t_up, k, up_blob))

            # ---- straggler mitigation: emergent from the channel ------------
            arrivals.sort(key=lambda a: a[0])
            survivors = [a for a in arrivals if a[0] <= deadline]
            if not survivors:            # never lose a round: keep the fastest one
                survivors = [arrivals[0]]
            # uploads that arrived but missed the barrier: paid-for waste.
            # survivors is always a prefix of the time-sorted arrivals.
            dropped_blob_bytes += sum(
                len(a[2]) for a in arrivals[len(survivors):]
            )
            n_dropped = len(pre) - len(survivors)
            dropped_hist.append(n_dropped)
            parts_hist.append(len(survivors))
            # sync barrier: no drops → the last survivor closes the round; any
            # drop → the server waited out the full deadline (and, in the
            # all-dropped fallback, for the fastest client beyond it).
            last_survivor = max(a[0] for a in survivors)
            round_times.append(
                wait_s + (max(deadline, last_survivor) if n_dropped
                          else last_survivor)
            )
            t_now += round_times[-1]

            # ---- ingest gate (content defense) ------------------------------
            # Survivors cleared framing/CRC/deadline; the gate now vets their
            # CONTENT. Quarantined uploads were shipped and paid for, so their
            # bytes are booked as upload AND as quarantine — the third ledger
            # outcome next to ingested and dropped.
            if gate is not None:
                accepted = []
                for total, k, up_blob in survivors:
                    gated_bytes += len(up_blob)
                    if gate.check(up_blob).ok:
                        accepted.append((total, k, up_blob))
                    else:
                        up_bytes += len(up_blob)
                        if tier is not None:
                            tier.note_quarantined(len(up_blob))
                survivors = accepted

            # ---- aggregation (server decodes the real upstream buffers) -----
            with obs.span("repro.round.aggregate"):
                if not survivors:
                    # every arrival was quarantined: hold the model this round
                    # (losing a round to a poisoned cohort beats folding it in).
                    pass
                elif tier is not None:
                    # hierarchical: survivors fan into their regional edges; each
                    # edge ships one (optionally re-quantized) record to the root.
                    # The edge→root hop is real wire traffic, booked as upload.
                    for total, k, up_blob in survivors:
                        up_bytes += len(up_blob)
                        tier.add(k, up_blob, weight=len(clients[k]))
                    global_params, fold_info = tier.fold()
                    up_bytes += fold_info["edge_to_root_bytes"]
                elif cfg.fused_aggregation:
                    # streaming fused fan-in: zero-copy record decode into stacked
                    # packed buffers, one Pallas launch per chunk_c clients — the
                    # per-client dense trees of the reference loop never exist.
                    agg = Aggregator(chunk_c=cfg.agg_chunk_c, rule=rule,
                                     trim_frac=trim_frac)
                    for total, k, up_blob in survivors:
                        up_bytes += len(up_blob)
                        agg.add(up_blob, weight=len(clients[k]))
                    global_params = agg.finalize()
                else:
                    updates = []
                    for total, k, up_blob in survivors:
                        up_bytes += len(up_blob)
                        updates.append(TernaryUpdate(
                            payload=decode_update(up_blob),
                            n_samples=len(clients[k]),
                            client_id=k,
                        ))
                    global_params = server_aggregate(updates)

            up_bytes_per_round.append(up_bytes - round_up0)

        if (r + 1) % eval_every == 0 or r == cfg.rounds - 1:
            acc, ls = eval_fn(global_params)
            acc_hist.append(float(acc))
            loss_hist.append(float(ls))

    summary = channel.summary()
    telemetry = {
        # every straggler (pre-skipped before training OR arrived past
        # the deadline); the bytes cover only the latter — pre-skipped
        # clients never uploaded, so they waste no wire bytes.
        "dropped_updates": int(sum(dropped_hist)),
        "dropped_update_bytes": dropped_blob_bytes,
        "retrans_bytes": summary.get("retrans_bytes", 0),
        "retries": summary.get("retries", 0),
        "goodput_fraction": summary.get("goodput_fraction", 1.0),
        "availability": cfg.availability.kind,
        # upstream wire bytes booked per round (client hop + any edge→root
        # hop) — the bytes-to-target-accuracy benches integrate this.
        "upload_bytes_per_round": up_bytes_per_round,
        "download_bytes_per_round": down_bytes_per_round,
    }
    if ctrl is not None:
        telemetry["controller"] = ctrl.telemetry()
    if gate is not None:
        telemetry["defense"] = gate.telemetry()
        # extended ledger at the gate: every survivor byte presented is
        # either ingested (passed) or quarantined — nothing leaks.
        telemetry["defense"]["ledger_balanced"] = (
            gated_bytes == gate.passed_bytes + gate.quarantined_bytes
        )
    if tier is not None:
        telemetry["hierarchy"] = tier.telemetry()
    return FedResult(
        accuracy=acc_hist,
        loss=loss_hist,
        upload_bytes=up_bytes,
        download_bytes=down_bytes,
        rounds_run=cfg.rounds,
        participants_per_round=parts_hist,
        round_times=round_times,
        dropped_per_round=dropped_hist,
        transfer_summary=summary,
        telemetry=telemetry,
    )


def run_federated(
    apply_fn: Callable,
    global_params: Pytree,
    clients: list[ClientDataset],
    cfg: FedConfig,
    optimizer: Optimizer,
    eval_fn: Callable[[Pytree], tuple[float, float]],
    *,
    eval_every: int = 10,
) -> FedResult:
    """Unified entry point: dispatches on ``cfg.mode``.

    - "sync":  Algorithm 2's round-synchronous server (this module).
    - "async": event-driven buffered-asynchronous server
               (``fed.async_server``, FedBuf-style).
    """
    if cfg.mode == "async":
        from repro.fed.async_server import run_federated_async

        return run_federated_async(
            apply_fn, global_params, clients, cfg, optimizer, eval_fn,
            eval_every=eval_every,
        )
    if cfg.mode != "sync":
        raise ValueError(f"unknown federated mode {cfg.mode!r}")
    return run_federated_sync(
        apply_fn, global_params, clients, cfg, optimizer, eval_fn,
        eval_every=eval_every,
    )
