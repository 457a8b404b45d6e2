"""Streaming fused fan-in aggregation for the T-FedAvg server.

``Aggregator`` replaces the dequantize-every-client Python loop
(``core.tfedavg.server_aggregate`` stays as the list-based REFERENCE): wire
blobs stream in one at a time (``add``), their ternary records are decoded
ZERO-COPY (read-only numpy views into the caller's immutable ``bytes`` blob;
a mutable buffer is copied once; no per-client device transfer), staged at
each flush into reusable stacked ``(chunk, R, LANES)`` uint8 buffers, and
every full chunk is folded into the running dense sum by ONE launch of the
fused Pallas kernel (``kernels.aggregate.packed_weighted_sum``, C-shardable
over a mesh via ``parallel.fanin``). ``finalize`` flushes the remainder and
returns the |D_k|-weighted mean pytree.

Why this is the fan-in artery:
  - per-client fp32 trees are never materialized — the only dense state is
    ONE running fp32 partial per leaf plus one chunk-sized byte buffer, so
    server memory is O(chunk + model), independent of the client count C;
  - per-client scales fold into the kernel's coefficient vector
    (coeff = |D_k| · w_q); leaves with per-leading-dim scales (stacked scan
    layers, conv kernels) aggregate per SCALE SEGMENT — each segment is a
    contiguous byte range of the wire stream, so the split is a zero-copy
    slice;
  - client counts vary round to round, so chunks are padded up to a BUCKET
    (powers of two up to ``chunk_c``; padding rows carry coefficient 0) —
    the jit trace set is the bucket set × leaf shapes, and a new client
    count never triggers a retrace (``parallel.fanin.fanin_trace_count``);
  - non-ternary wire leaves (raw fp32 biases, downcast, top-k — whatever
    the upstream codec spec shipped) take a streaming dequant fallback with
    the same O(chunk) footprint.

Equivalence: Σ w_c·(s_c·codes_c) is computed as Σ (w_c·s_c)·codes_c in fp32
— bit-order differs from the reference's per-client dequant-then-sum, so
parity is within ~1e-6·C, not bit-exact (``tests/test_aggregate.py``).

Robust rules (``rule=`` ctor arg; "mean" is the default and bit-identical
to the pre-rule aggregator):
  - "majority": ternary leaves are decided coordinate-wise by weighted
    plurality over the 2-bit codes — ``kernels.vote`` counts ±1 vote
    masses straight off the same stacked byte buffers (scales NOT folded:
    a vote is scale-free), partial counts accumulate across chunk flushes,
    and ``finalize`` multiplies the winner codes by a per-segment robust
    scale (the weighted MEDIAN of the client scales, so a scale-poisoning
    minority cannot move it). Non-ternary leaves take the coordinate-wise
    weighted median.
  - "trimmed_mean" / "median": every leaf is decoded dense and kept
    per-client (O(C·model) memory — exact order statistics need the full
    sample; these rules are for moderate C), then reduced coordinate-wise.
A sign-flipping / noise-injecting minority with under half the total vote
weight cannot move any majority-voted coordinate (``tests/test_robust.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.comm.wire import decode_update_leaves, tree_from_records
from repro.core.compression import decode_wire_leaf
from repro.core.ternary import TernaryTensor
from repro.kernels.aggregate import BLOCK_ROWS, LANES, padded_rows
from repro.kernels.vote import majority_from_counts
from repro.parallel.fanin import fanin_vote_counts, fanin_weighted_sum

Pytree = Any

# Aggregation rules; "mean" is the legacy bit-exact weighted mean, the rest
# are the Byzantine-robust statistics (see module docstring).
AGG_RULES = ("mean", "majority", "trimmed_mean", "median")


def weighted_median(stack: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """Coordinate-wise weighted median along axis 0 (lower median: the
    first sorted value whose cumulative weight reaches half the total)."""
    order = np.argsort(stack, axis=0, kind="stable")
    svals = np.take_along_axis(stack, order, axis=0)
    sw = np.take_along_axis(
        np.broadcast_to(
            weights.reshape((-1,) + (1,) * (stack.ndim - 1)), stack.shape
        ), order, axis=0,
    )
    cum = np.cumsum(sw, axis=0)
    idx = np.argmax(cum >= cum[-1] / 2.0, axis=0)
    return np.take_along_axis(svals, idx[None], axis=0)[0]


def trimmed_mean(stack: np.ndarray, weights: np.ndarray,
                 trim_frac: float) -> np.ndarray:
    """Coordinate-wise trimmed weighted mean along axis 0: sort values,
    drop ⌊trim_frac·C⌋ per side (clamped so at least one survives), then
    the weighted mean of the survivors — the classic defense against a
    tail-dwelling minority."""
    c = stack.shape[0]
    k = min(int(trim_frac * c), (c - 1) // 2)
    order = np.argsort(stack, axis=0, kind="stable")
    svals = np.take_along_axis(stack, order, axis=0)
    sw = np.take_along_axis(
        np.broadcast_to(
            weights.reshape((-1,) + (1,) * (stack.ndim - 1)), stack.shape
        ), order, axis=0,
    )
    if k:
        svals, sw = svals[k:c - k], sw[k:c - k]
    return (svals * sw).sum(axis=0) / sw.sum(axis=0)


def bucket_for(c: int, chunk_c: int) -> int:
    """Pad a partial chunk of c clients up to the trace bucket: the smallest
    power of two ≥ c, capped at ``chunk_c`` (full chunks hit chunk_c; the
    cap also holds for non-power-of-two chunk sizes)."""
    if c >= chunk_c:
        return chunk_c
    b = 1
    while b < c:
        b <<= 1
    return min(b, chunk_c)


@dataclasses.dataclass
class _Group:
    """Pending rows of one (leaf, scale-segment) stacked kernel input."""

    nbytes: int                  # real packed bytes per client segment
    n_elements: int              # logical elements per segment
    rows: int                    # padded byte-rows R (multiple of BLOCK_ROWS)
    views: list = dataclasses.field(default_factory=list)   # np byte views
    coeffs: list = dataclasses.field(default_factory=list)  # weight · scale
    partial: Any = None          # running fp32 flat sum (jax array)
    # majority-rule state: running (2, 4R·LANES) ±1 vote masses, plus every
    # client's (scale, weight) sample for the finalize-time robust scale
    # (persists across flushes — the median needs the full sample).
    counts: Any = None
    scale_samples: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _LeafPlan:
    """How one record path aggregates: fused kernel groups or dense fallback."""

    fused: bool
    shape: tuple = ()
    dtype: str = "float32"
    n_segments: int = 1
    scale_size: int = 1


class Aggregator:
    """Streaming |D_k|-weighted mean of wire-encoded client updates.

    Usage::

        agg = Aggregator(chunk_c=16)
        for blob, n_samples in arrivals:
            agg.add(blob, weight=n_samples)
        global_params = agg.finalize()

    One instance aggregates one round/buffer at a time, but is REUSABLE:
    ``finalize(reset=True)`` (or an explicit ``reset()``) clears the
    accumulated state while KEEPING the leaf plans and the stacked staging
    buffers, so a long-lived server instance — e.g. the buffered-async
    server, which aggregates every K arrivals — never rebuilds its
    buffers between mixes.
    """

    def __init__(self, chunk_c: int = 16, *, mesh=None,
                 block_rows: int = BLOCK_ROWS, interpret: bool | None = None,
                 rule: str = "mean", trim_frac: float = 0.2):
        if chunk_c < 1:
            raise ValueError(f"chunk_c must be ≥ 1, got {chunk_c}")
        if rule not in AGG_RULES:
            raise ValueError(f"rule must be one of {AGG_RULES}, got {rule!r}")
        if not 0.0 <= trim_frac < 0.5:
            raise ValueError(f"trim_frac must be in [0, 0.5), got {trim_frac}")
        self.chunk_c = chunk_c
        self.mesh = mesh
        self.block_rows = block_rows
        self.interpret = interpret
        self.rule = rule
        self.trim_frac = trim_frac
        # exact order statistics need every client's dense leaf — these two
        # rules bypass the fused plan entirely (O(C·model) memory).
        self._dense_rule = rule in ("trimmed_mean", "median")
        self._client_dense: dict[str, list] = {}  # path → [(weight, f32 leaf)]
        self._paths: list[str] | None = None   # record order of client 0
        self._plans: dict[str, _LeafPlan] = {}
        self._groups: dict[tuple[str, int], _Group] = {}
        self._fallback: dict[str, np.ndarray] = {}
        # paths whose fallback accumulator received adds SINCE THE LAST
        # reset — a long-lived aggregator keeps (zeroed) accumulators from
        # past mixed-codec mixes, and finalize must not fold those into
        # later pure-ternary mixes.
        self._fallback_touched: set[str] = set()
        self._fallback_dtype: dict[str, Any] = {}
        self._buffers: dict[tuple[int, int], np.ndarray] = {}  # reusable
        self._pending = 0
        self._n_clients = 0
        self._total_weight = 0.0
        self.peak_intermediate_bytes = 0
        # drop-path ledger: updates the server PAID wire bytes for but chose
        # not to fold in (staleness cap, policy drops). Cumulative across
        # resets — it is run-level waste accounting, not per-mix state.
        self.dropped_updates = 0
        self.dropped_bytes = 0
        # quarantine ledger: updates the defense gate refused — received and
        # paid for, but content-poisoned. Third ledger bucket; cumulative
        # across resets like the drop counters.
        self.quarantined_updates = 0
        self.quarantined_bytes = 0

    # -- ingest ------------------------------------------------------------

    def note_dropped(self, nbytes: int) -> None:
        """Record one received-but-discarded update (e.g. past the async
        staleness cap): its wire bytes were spent, its weights never enter
        the mean. Feeds the scenario telemetry's waste accounting."""
        self.dropped_updates += 1
        self.dropped_bytes += int(nbytes)

    def note_quarantined(self, nbytes: int) -> None:
        """Record one gate-refused update: wire bytes spent, content judged
        poisoned, weights never enter the aggregate. Extends the ledger
        invariant to shipped == ingested + dropped + quarantined."""
        self.quarantined_updates += 1
        self.quarantined_bytes += int(nbytes)

    def add(self, blob: bytes, weight: float) -> None:
        """Decode one client's wire buffer (zero-copy) and buffer/accumulate
        it; a full chunk triggers one fused kernel launch per leaf group.

        The pending ternary records are views into ``blob`` itself when it is
        ``bytes`` (each keeps the whole blob alive until the chunk flushes);
        any other buffer is copied once, so the caller may refill it."""
        with obs.span("repro.agg.add"):
            if weight < 0:
                raise ValueError(f"client weight must be ≥ 0, got {weight}")
            # weight 0 (an empty data shard) is tolerated exactly like the
            # reference: the client rides along contributing nothing.
            pairs = decode_update_leaves(blob, zero_copy=True)
            paths = [p for p, _ in pairs]
            if len(set(paths)) != len(paths):
                # decode_update would last-wins this; an accumulator would
                # double-count it — refuse loudly (it is a malformed update).
                from repro.comm.wire import WireError

                raise WireError("duplicate record paths in client update")
            if self._paths is None:
                self._paths = paths
                for path, leaf in pairs:
                    self._plan_leaf(path, leaf)
            elif paths != self._paths:
                raise ValueError(
                    "client update structure changed mid-aggregation: "
                    f"{len(paths)} records vs {len(self._paths)}"
                )
            for path, leaf in pairs:
                self._add_leaf(path, leaf, float(weight))
            self._total_weight += float(weight)
            self._n_clients += 1
            self._pending += 1
            if self._pending >= self.chunk_c:
                self._flush()

    def _plan_leaf(self, path: str, leaf) -> None:
        if self._dense_rule:
            # trimmed_mean / median: every leaf keeps per-client dense
            # copies; the fused plan never engages.
            self._plans[path] = _LeafPlan(fused=False)
            return
        if isinstance(leaf, TernaryTensor):
            shape = tuple(int(s) for s in leaf.shape)
            n = leaf.n_elements
            scale = np.asarray(leaf.w_q)
            trailing_ok = scale.ndim <= 1 or all(s == 1 for s in scale.shape[1:])
            if scale.size == 1:
                segs = 1
            elif (trailing_ok and shape and scale.size == shape[0]
                  and n % scale.size == 0 and (n // scale.size) % 4 == 0):
                segs = scale.size   # per-leading-dim scales, byte-aligned
            else:
                segs = 0            # odd scale layout → dense fallback
            if segs:
                self._plans[path] = _LeafPlan(
                    fused=True, shape=shape, dtype=leaf.dtype,
                    n_segments=segs, scale_size=scale.size,
                )
                seg_elems = n // segs
                seg_bytes = (seg_elems + 3) // 4 if segs == 1 else seg_elems // 4
                rows = padded_rows(seg_bytes, self.block_rows)
                for s in range(segs):
                    self._groups[(path, s)] = _Group(
                        nbytes=seg_bytes, n_elements=seg_elems, rows=rows
                    )
                return
        self._plans[path] = _LeafPlan(fused=False)

    def _add_leaf(self, path: str, leaf, weight: float) -> None:
        plan = self._plans[path]
        if plan.fused and not isinstance(leaf, TernaryTensor):
            # mixed-codec round: this client shipped a different wire kind
            # (top-k, downcast, raw) for a path planned fused off an earlier
            # ternary client. The weighted MEAN is additive, so the leaf
            # detours through the dense fallback accumulator and finalize
            # sums the fused partial with it; the order-statistic rules have
            # no such decomposition — refuse loudly rather than vote wrong.
            if self.rule != "mean":
                raise ValueError(
                    f"leaf {path!r}: mixed wire kinds under rule "
                    f"{self.rule!r} (only 'mean' aggregates mixed-codec "
                    "rounds; pin one codec per round for robust rules)"
                )
            self._add_fallback(path, leaf, weight)
            return
        if plan.fused:
            t: TernaryTensor = leaf
            if tuple(int(s) for s in t.shape) != plan.shape:
                raise ValueError(f"leaf {path!r} changed shape mid-aggregation")
            packed = np.asarray(t.packed).reshape(-1)
            scale = np.asarray(t.w_q, np.float64).reshape(-1)
            if scale.size != plan.scale_size:
                raise ValueError(f"leaf {path!r} changed scale layout")
            for s in range(plan.n_segments):
                g = self._groups[(path, s)]
                g.views.append(packed[s * g.nbytes:(s + 1) * g.nbytes])
                if self.rule == "majority":
                    # votes are scale-free: the kernel coefficient is the
                    # raw weight; the scale joins at finalize as a weighted
                    # median over these samples.
                    g.coeffs.append(weight)
                    g.scale_samples.append(
                        (float(scale[s if scale.size > 1 else 0]), weight)
                    )
                else:
                    g.coeffs.append(weight * float(scale[s if scale.size > 1 else 0]))
        else:
            self._add_fallback(path, leaf, weight)

    def _add_fallback(self, path: str, leaf, weight: float) -> None:
        with obs.span("repro.agg.dense"):
            dense = np.asarray(decode_wire_leaf(leaf))
            if path not in self._fallback_dtype:
                # reference promotion: float leaves keep their dtype under a
                # python-float weight, int leaves promote to float32.
                self._fallback_dtype[path] = (
                    dense.dtype if jnp.issubdtype(dense.dtype, jnp.floating)
                    else np.dtype(np.float32)
                )
            if self.rule == "mean":
                if path not in self._fallback:
                    self._fallback[path] = np.zeros(dense.shape, np.float32)
                self._fallback[path] += weight * dense.astype(np.float32)
                self._fallback_touched.add(path)
            else:
                # robust order statistics need the whole per-client sample.
                self._client_dense.setdefault(path, []).append(
                    (weight, dense.astype(np.float32))
                )

    # -- kernel launches ---------------------------------------------------

    def _buffer(self, c_pad: int, rows: int) -> np.ndarray:
        buf = self._buffers.get((c_pad, rows))
        if buf is None:
            buf = np.empty((c_pad, rows * LANES), np.uint8)
            self._buffers[(c_pad, rows)] = buf
            live = sum(b.nbytes for b in self._buffers.values())
            self.peak_intermediate_bytes = max(self.peak_intermediate_bytes, live)
        return buf

    def _flush(self) -> None:
        with obs.span("repro.agg.flush"):
            for g in self._groups.values():
                self._flush_group(g)
        self._pending = 0

    def _flush_group(self, g: _Group) -> None:
        c = len(g.views)
        if c == 0:
            return
        c_pad = bucket_for(c, self.chunk_c)
        buf = self._buffer(c_pad, g.rows)
        with obs.span("repro.agg.stage"):
            for i, v in enumerate(g.views):
                buf[i, :g.nbytes] = v
                buf[i, g.nbytes:] = 0
            buf[c:] = 0
            coeffs = np.zeros((c_pad,), np.float32)
            coeffs[:c] = g.coeffs
        with obs.span("repro.agg.transfer"):
            # the put of the staging buffer may be ZERO-COPY (the CPU backend
            # aliases aligned numpy memory), and the launch below is async
            stacked, coeffs = jax.device_put((buf.reshape(c_pad, g.rows, LANES), coeffs))
        # the launch ends at its block, which has to be there: the in-flight
        # kernel must be done with the buffer before the next group or chunk
        # refills it, or it would read torn bytes
        with obs.span("repro.agg.launch"):
            # majority: a zero-padding BYTE is four code-0 slots (−1 votes);
            # the zeroed coefficient rows cancel them exactly as in the mean
            # path, and real clients' tail padding lands past n_elements.
            fanin = fanin_vote_counts if self.rule == "majority" else fanin_weighted_sum
            out = fanin(stacked, coeffs, mesh=self.mesh, block_rows=self.block_rows,
                        interpret=self.interpret)
            out.block_until_ready()
        obs.count("agg.launches")
        if self.rule == "majority":
            g.counts = out if g.counts is None else g.counts + out
        else:
            g.partial = out if g.partial is None else g.partial + out
        g.views.clear()
        g.coeffs.clear()

    # -- result ------------------------------------------------------------

    @property
    def n_clients(self) -> int:
        """Client updates added since construction / the last reset."""
        return self._n_clients

    def reset(self) -> None:
        """Clear the accumulated state for the next aggregation while
        KEEPING the record plans and the reusable staging buffers — the
        long-lived-server path (async ``buffer_k`` mixes) pays the buffer
        allocation once, not every K arrivals."""
        for g in self._groups.values():
            g.views.clear()
            g.coeffs.clear()
            g.partial = None
            g.counts = None
            g.scale_samples.clear()
        for acc in self._fallback.values():
            acc.fill(0.0)
        self._fallback_touched.clear()
        for samples in self._client_dense.values():
            samples.clear()
        self._pending = 0
        self._n_clients = 0
        self._total_weight = 0.0

    def finalize(self, *, reset: bool = False) -> Pytree:
        """Flush pending rows and return the weighted-mean pytree
        (Algorithm 2's Σ |D_k|/Σ|D_k| · dequant(payload_k)). With
        ``reset=True`` the instance is immediately reusable for the next
        round (plans + staging buffers survive)."""
        with obs.span("repro.agg.finalize"):
            if self._n_clients == 0:
                raise ValueError("Aggregator.finalize: no client updates were added")
            if self._total_weight <= 0:
                raise ValueError("Aggregator.finalize: total client weight is zero")
            self._flush()
            inv = 1.0 / self._total_weight
            pairs = []
            for path in self._paths:
                plan = self._plans[path]
                if plan.fused and self.rule == "majority":
                    parts = []
                    for s in range(plan.n_segments):
                        g = self._groups[(path, s)]
                        counts = np.asarray(g.counts)[:, : g.n_elements]
                        votes = majority_from_counts(counts, self._total_weight)
                        vals = np.array([v for v, _ in g.scale_samples], np.float32)
                        ws = np.array([w for _, w in g.scale_samples], np.float32)
                        robust_scale = weighted_median(vals, ws)
                        parts.append(votes.astype(np.float32) * np.float32(robust_scale))
                    flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
                    leaf = jnp.asarray(flat.reshape(plan.shape)).astype(plan.dtype)
                elif plan.fused:
                    parts = []
                    for s in range(plan.n_segments):
                        g = self._groups[(path, s)]
                        # a mixed-codec round may leave a fused group empty
                        # (every client detoured to the fallback): zero partial.
                        parts.append(
                            g.partial[: g.n_elements] if g.partial is not None
                            else jnp.zeros((g.n_elements,), jnp.float32)
                        )
                    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                    if path in self._fallback_touched:
                        # mixed-codec detours accumulated Σ w·dense here; the
                        # weighted mean is additive across the two routes.
                        flat = flat + jnp.asarray(self._fallback[path].reshape(-1))
                    leaf = (flat * inv).reshape(plan.shape).astype(plan.dtype)
                elif self.rule == "mean":
                    acc = self._fallback[path] * np.float32(inv)
                    leaf = jnp.asarray(acc).astype(self._fallback_dtype[path])
                else:
                    samples = self._client_dense[path]
                    stack = np.stack([d for _, d in samples])
                    ws = np.array([w for w, _ in samples], np.float32)
                    if self.rule == "trimmed_mean":
                        acc = trimmed_mean(stack, ws, self.trim_frac)
                    else:  # "median", and the majority rule's dense fallback
                        acc = weighted_median(stack, ws)
                    leaf = jnp.asarray(acc).astype(self._fallback_dtype[path])
                pairs.append((path, leaf))
            out = tree_from_records(pairs)
        if reset:
            self.reset()
        return out
