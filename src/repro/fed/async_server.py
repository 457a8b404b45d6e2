"""Event-driven buffered-asynchronous federated server (FedBuf-style).

The synchronous server (Algorithm 2) pays a barrier per round: every
participant waits for the slowest survivor. At fleet scale that barrier is
the throughput ceiling, so this server removes it:

  - ``max_concurrency`` clients are always in flight. Each one downloads
    the current global model (serialized through ``repro.comm.wire``),
    trains locally, and uploads; its arrival time is download + compute +
    upload from the ``repro.comm.channel`` model. Uploads go through
    ``Channel.transfer_timed``, so simultaneous async arrivals contend for
    the server NIC instead of each enjoying the full pipe.
  - Refill draws sample from the clients ONLINE at dispatch time
    (``FedConfig.availability`` — diurnal churn, trace replay, or the
    always-on fleet, which reproduces pre-scenario runs bit-exactly). If
    nobody is reachable, simulated time advances to the next availability
    change before dispatching.
  - Arrivals are processed from an event queue in simulated-time order.
    The server BUFFERS them and aggregates every ``buffer_k`` arrivals —
    never blocking on any individual client.
  - An arrival carries the version of the model it started from; its
    aggregation weight is discounted by staleness,
        w_i ∝ |D_i| · (1 + staleness_i)^(-α)          (α = staleness_exponent)
    and the buffer average is mixed into the global model with rate η:
        θ ← (1-η)·θ + η·Σ ŵ_i·θ_i .
    With fresh updates (staleness 0), η = 1 and K = concurrency this
    reduces exactly to the synchronous weighted average.
  - A hard staleness cap (``max_staleness``, 0 = off) bounds how old an
    update may be: past the cap it is DROPPED (``staleness_policy="drop"``
    — its bytes were still paid for and are accounted as waste) or
    down-weighted by an extra ``(1+excess)^(-α)`` factor ("downweight").
  - ``adaptive_buffer`` turns the fixed ``buffer_k`` into a controller:
    an EWMA of inter-arrival gaps estimates the arrival rate and
    ``buffer_k ← clamp(round(target_mix_latency_s / gap), 1, concurrency)``
    retunes after every mix, holding the time-per-aggregation near the
    target as churn moves the arrival rate. ``target_mix_latency_s = 0``
    locks the target to the initial K's observed latency on first mix.

Bytes are measured from the serialized buffers on both directions; transfer
times are logged per client, so the async-vs-sync comparison reads out in
simulated seconds as well as bytes. Compression is per-direction
(``FedConfig.compression``): dispatch serializes through the DOWNSTREAM
codec spec and arrivals through the UPSTREAM one (via the shared
``broadcast_blob`` / ``train_client`` helpers). Arrivals stream straight
into ONE long-lived ``fed.aggregator.Aggregator`` — zero-copy record
ingest, the fused packed fan-in kernel for ternary records, codec-registry
dequant for everything else — whose staging buffers and leaf plans persist
ACROSS mixes (``finalize(reset=True)`` every ``buffer_k`` arrivals), so
asymmetric up/down codecs meter correctly, the buffer is never expanded to
per-client dense trees, and nothing is re-allocated per aggregation
(``cfg.fused_aggregation=False`` restores the reference dequant loop over
a buffered blob list). Per-mix telemetry — staleness histogram, dropped /
retransmitted bytes, the buffer_k trajectory — lands in
``FedResult.telemetry``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import numpy as np

from repro.comm import Channel
from repro.comm.wire import decode_update
from repro.data.federated import ClientDataset
from repro.fed.aggregator import Aggregator
from repro.fed.attackers import attacker_ids, poison_blob
from repro.fed.availability import draw_one, draw_participants, make_availability
from repro.fed.controller import make_controller
from repro.fed.defense import UpdateGate
from repro.fed.fleet import EventHeap
from repro.fed.hierarchy import EdgeTier
from repro.fed.simulation import (
    FedConfig,
    FedResult,
    make_local_steps,
    broadcast_blob,
    dequantize_tree,
    receive_broadcast,
    resolve_rule,
    train_client,
)
from repro.optim import Optimizer

Pytree = Any


def _weighted_mix(global_params, buffered, eta, cfg: FedConfig | None = None,
                  agg: Aggregator | None = None):
    """θ ← (1-η)·θ + η·Σ ŵ_i·dequant(blob_i) over the buffered arrivals.

    ``buffered`` holds (staleness-discounted weight, wire blob) pairs; the
    weighted mean streams through the fused aggregator (Σ ŵ normalizes
    inside ``finalize``), then mixes into the global with rate η. Passing a
    long-lived ``agg`` reuses its staging buffers (``finalize(reset=True)``)
    instead of constructing a fresh one per mix.
    """
    if cfg is None or cfg.fused_aggregation:
        if agg is None:
            agg = Aggregator(chunk_c=cfg.agg_chunk_c if cfg is not None else 16)
        for w, blob in buffered:
            agg.add(blob, weight=w)
        mean = agg.finalize(reset=True)
    else:
        raw = np.array([w for w, _ in buffered], dtype=np.float64)
        wts = raw / raw.sum()
        models = [dequantize_tree(decode_update(b)) for _, b in buffered]

        def wsum(*leaves):
            acc = leaves[0] * wts[0]
            for w, l in zip(wts[1:], leaves[1:]):
                acc = acc + w * l
            return acc

        mean = jax.tree_util.tree_map(wsum, *models)

    return jax.tree_util.tree_map(
        lambda g, m: (1.0 - eta) * g + eta * m, global_params, mean
    )


def run_federated_async(
    apply_fn: Callable,
    global_params: Pytree,
    clients: list[ClientDataset],
    cfg: FedConfig,
    optimizer: Optimizer,
    eval_fn: Callable[[Pytree], tuple[float, float]],
    *,
    eval_every: int = 10,
) -> FedResult:
    """Run ``cfg.rounds`` buffered aggregations; see module docstring."""
    rng = np.random.default_rng(cfg.seed)
    fp_step, qat_step = make_local_steps(apply_fn, optimizer, cfg)
    channel = Channel(cfg.channel, len(clients), seed=cfg.seed + 1)
    avail = make_availability(cfg.availability, len(clients), seed=cfg.seed)

    n_conc = cfg.max_concurrency or max(
        int(np.ceil(cfg.participation * len(clients))), 1
    )
    n_conc = min(n_conc, len(clients))
    buffer_k = max(1, min(cfg.buffer_k, n_conc))
    max_stale = cfg.max_staleness if cfg.max_staleness > 0 else float("inf")
    if cfg.staleness_policy not in ("drop", "downweight"):
        raise ValueError(
            f"unknown staleness_policy {cfg.staleness_policy!r} "
            "(expected 'drop' or 'downweight')"
        )

    version = 0
    up_bytes = 0
    down_bytes = 0
    # arrival events: array-backed min-heap keyed (arrival_time, seq) —
    # the internal seq is assigned in push order, so pops come out in the
    # EXACT order the old (time, seq, ...) tuple heapq produced.
    events = EventHeap(capacity=max(2 * n_conc, 16))
    buffered: list = []           # (weight, wire blob) — reference path only
    rule, trim_frac = resolve_rule(cfg)
    # hierarchical tier (when enabled): arrivals fan into regional edges,
    # each shipping one re-quantized record to the root per mix.
    tier = (EdgeTier(cfg.hierarchy, cfg.fttq, len(clients),
                     fused_encode=cfg.fused_encode,
                     rule=rule, trim_frac=trim_frac)
            if cfg.hierarchy.enabled else None)
    # ONE long-lived aggregator for the whole run: arrivals stream into it
    # as they land and `finalize(reset=True)` every buffer_k keeps its
    # staging buffers + leaf plans alive across mixes (ROADMAP item).
    agg = (Aggregator(chunk_c=cfg.agg_chunk_c, rule=rule, trim_frac=trim_frac)
           if cfg.fused_aggregation and tier is None else None)
    # Byzantine layer: seeded attacker cohort poisons at dispatch; the gate
    # vets every arrival's CONTENT before it can enter the buffer. The gate
    # is long-lived so its scale history warms across the whole run.
    attackers = (attacker_ids(cfg.attack, len(clients))
                 if cfg.attack is not None else frozenset())
    gate = (UpdateGate(cfg.defense, global_params)
            if cfg.defense is not None and cfg.defense.enabled else None)
    # adaptive compression controller (None → static codec path, bit-exact).
    # Encodes are tagged with the model version they trained from.
    ctrl = make_controller(cfg)
    if ctrl is not None and rule != "mean":
        raise ValueError(
            "adaptive compression requires aggregation rule 'mean': "
            "mixed-codec rounds have no robust-vote decomposition"
        )
    arrived_bytes = 0             # client-hop bytes presented to the gate
    n_buffered = 0
    acc_hist, loss_hist = [], []
    agg_times, staleness_hist, parts_hist = [], [], []
    # drop-path ledger for the reference (non-fused) path; the fused path
    # books waste on the long-lived Aggregator itself (note_dropped).
    dropped_updates = 0
    dropped_update_bytes = 0
    last_agg_t = 0.0
    # adaptive buffer_k controller state: EWMA of inter-arrival gaps.
    ewma_gap: float | None = None
    last_arrival = 0.0
    auto_target = 0.0             # resolved target when target_mix_latency_s=0

    # the broadcast only changes when an aggregation bumps `version`, so
    # serialize (requantize + encode) and decode once per version, not per
    # dispatch.
    blob_cache = {"version": -1, "blob": b"", "params": None}

    def current_broadcast() -> tuple[bytes, Any]:
        if blob_cache["version"] != version:
            blob_cache["blob"] = broadcast_blob(global_params, cfg)
            blob_cache["params"] = receive_broadcast(blob_cache["blob"])
            blob_cache["version"] = version
        return blob_cache["blob"], blob_cache["params"]

    def dispatch(k: int, t0: float, clock: float | None = None) -> None:
        """Send the CURRENT global to client k; enqueue its arrival.

        ``clock`` is the event-loop pop time (monotonic across dispatches)
        — the safe prune horizon for the NIC contention window. ``t0`` may
        run ahead of it when an empty fleet forced a wait.
        """
        nonlocal down_bytes
        blob, start_params = current_broadcast()
        down_bytes += len(blob)
        if ctrl is not None:
            ctrl.note_round(version)
        up_blob = train_client(
            clients[k], start_params, cfg, optimizer, fp_step, qat_step,
            rng, controller=ctrl, client_id=k,
        )
        if k in attackers:
            # poison at dispatch (wire-valid re-encode); colluding cohorts
            # key their rng on the model version they trained from.
            up_blob = poison_blob(up_blob, cfg.attack, k, round_idx=version)
        t_down = channel.transfer(k, len(blob), "down")
        t_comp = channel.compute_time(k, len(clients[k]) * cfg.local_epochs)
        # async uploads share the server NIC: the upload's absolute start
        # time lets in-flight arrivals degrade each other's rate.
        t_up = channel.transfer_timed(
            k, len(up_blob), t0 + t_down + t_comp, "up",
            now_s=t0 if clock is None else clock,
        )
        if ctrl is not None:
            ctrl.observe_upload(k, len(up_blob), t_up)
        total = t_down + t_comp + t_up
        events.push(t0 + total, (k, up_blob, version))

    def refill(now: float) -> None:
        """Dispatch one ONLINE client; advance time if nobody is reachable.
        The availability clock ``t`` may run ahead of ``now``, but pending
        heap events can still pop before it — so ``now`` (monotonic across
        refills) stays the channel's prune horizon."""
        t = now
        while True:
            k = draw_one(avail, t, len(clients), rng)
            if k >= 0:
                dispatch(k, t, clock=now)
                return
            t = avail.next_change(t)
            if not np.isfinite(t):
                raise RuntimeError("no client is ever available")

    t0 = 0.0
    start = draw_participants(avail, t0, n_conc, len(clients), rng)
    while start.size == 0:
        t0 = avail.next_change(t0)
        if not np.isfinite(t0):
            raise RuntimeError("no client is ever available")
        start = draw_participants(avail, t0, n_conc, len(clients), rng)
    for k in start:
        dispatch(int(k), t0, clock=0.0)

    while version < cfg.rounds:
        if len(events) == 0:  # pragma: no cover - dispatch() always refills
            raise RuntimeError("async server starved: no in-flight clients")
        now, _, (k, up_blob, born) = events.pop()
        up_bytes += len(up_blob)
        arrived_bytes += len(up_blob)
        staleness = version - born
        gap = now - last_arrival
        last_arrival = now
        ewma_gap = gap if ewma_gap is None else 0.8 * ewma_gap + 0.2 * gap

        if gate is not None and not gate.check(up_blob).ok:
            # content-poisoned: quarantined BEFORE staleness/weighting —
            # it never enters the buffer and never counts toward buffer_k.
            if agg is not None:
                agg.note_quarantined(len(up_blob))
            elif tier is not None:
                tier.note_quarantined(len(up_blob))
        elif staleness > max_stale and cfg.staleness_policy == "drop":
            staleness_hist.append(staleness)
            # the bytes were transferred and paid for; the update is waste.
            if agg is not None:
                agg.note_dropped(len(up_blob))
            else:
                dropped_updates += 1
                dropped_update_bytes += len(up_blob)
        else:
            staleness_hist.append(staleness)
            weight = len(clients[k]) * (
                (1.0 + staleness) ** (-cfg.staleness_exponent)
            )
            if staleness > max_stale:  # "downweight": extra excess discount
                weight *= (1.0 + staleness - max_stale) ** (
                    -cfg.staleness_exponent
                )
            if tier is not None:
                tier.add(k, up_blob, weight, staleness=float(staleness))
            elif agg is not None:
                agg.add(up_blob, weight=weight)  # streams into the aggregator
            else:
                buffered.append((weight, up_blob))
            n_buffered += 1

        if n_buffered >= buffer_k:
            if tier is not None:
                # edges flush ONE record each to the root; that hop is real
                # upstream wire traffic, booked alongside the client hop.
                mean, fold_info = tier.fold()
                up_bytes += fold_info["edge_to_root_bytes"]
                eta = cfg.mixing_rate
                global_params = jax.tree_util.tree_map(
                    lambda g, m: (1.0 - eta) * g + eta * m,
                    global_params, mean,
                )
            else:
                global_params = _weighted_mix(
                    global_params, buffered, cfg.mixing_rate, cfg, agg=agg
                )
            buffered = []
            n_buffered = 0
            version += 1
            parts_hist.append(buffer_k)
            agg_times.append(now - last_agg_t)
            last_agg_t = now
            if cfg.adaptive_buffer and ewma_gap and ewma_gap > 0:
                target = cfg.target_mix_latency_s
                if target <= 0:
                    if auto_target == 0.0:  # lock the initial K's latency
                        auto_target = ewma_gap * buffer_k
                    target = auto_target
                buffer_k = int(np.clip(round(target / ewma_gap), 1, n_conc))
            if version % eval_every == 0 or version == cfg.rounds:
                acc, ls = eval_fn(global_params)
                acc_hist.append(float(acc))
                loss_hist.append(float(ls))

        # keep the fleet saturated: replace the arrival with a fresh ONLINE
        # client, carrying the newest global.
        if version < cfg.rounds:
            refill(now)

    summary = channel.summary()
    if agg is not None:  # the fused path's waste ledger lives on the agg
        dropped_updates, dropped_update_bytes = (
            agg.dropped_updates, agg.dropped_bytes
        )
    telemetry = {
        "staleness_hist": np.bincount(
            np.asarray(staleness_hist, dtype=np.int64)
        ).tolist() if staleness_hist else [],
        "dropped_updates": dropped_updates,
        "dropped_update_bytes": dropped_update_bytes,
        # every mix fires at exactly buffer_k accepted arrivals, so the
        # participants history IS the adaptive-K trajectory.
        "buffer_k_per_agg": parts_hist,
        "retrans_bytes": summary.get("retrans_bytes", 0),
        "retries": summary.get("retries", 0),
        "goodput_fraction": summary.get("goodput_fraction", 1.0),
        "availability": cfg.availability.kind,
    }
    if ctrl is not None:
        telemetry["controller"] = ctrl.telemetry()
    if gate is not None:
        telemetry["defense"] = gate.telemetry()
        # extended ledger on the client hop: every arrived byte either
        # passed the gate (then ingested or staleness-dropped) or was
        # quarantined — the three buckets partition the hop exactly.
        telemetry["defense"]["ledger_balanced"] = (
            arrived_bytes == gate.passed_bytes + gate.quarantined_bytes
        )
    if tier is not None:
        telemetry["hierarchy"] = tier.telemetry()
    return FedResult(
        accuracy=acc_hist,
        loss=loss_hist,
        upload_bytes=up_bytes,
        download_bytes=down_bytes,
        rounds_run=version,
        participants_per_round=parts_hist,
        round_times=agg_times,
        dropped_per_round=[0] * version,
        transfer_summary=summary,
        staleness_per_agg=staleness_hist,
        telemetry=telemetry,
    )
