"""Fused client-egress encode: whole-tree ternary quantize→pack in O(few)
kernel launches.

This is the encode-side counterpart of ``fed.aggregator`` (PR 3's fused
fan-in): the paper's upstream step (§III.B Algorithm 2 — every client ships
2-bit I_t + w_q each round) and the server's downstream re-quantization both
used to run a per-leaf jnp chain with ~5 HBM passes of fp32 per tensor.
Here every quantizable leaf of an update is flattened into lane-aligned
staging (``kernels.quantize_pack.stage_encode``, one segment per leaf or per
stacked-scan layer) and the whole tree is encoded by

  - ONE ``quantize_pack_segments`` launch for all single-segment leaves of a
    dtype (per-block (denom, Δ) scalars ride in SMEM), plus
  - one ``quantize_pack_stacked`` launch per stacked (ndim ≥ 3)
    scan leaf with per-layer scales,

each fusing scale → threshold → ternarize → 2-bit-pack into one HBM read and
a ~1/16-size write, with the w_q numerator/denominator coming out of the
same pass as per-tile partial moments. The packed output IS the wire byte
stream: one host transfer per tree, sliced zero-copy into per-leaf
``TernaryTensor.packed`` views.

Bit-exactness: the fused payloads serialize BYTE-IDENTICAL to the pinned
jnp reference paths (``core.tfedavg.client_update_payload(fused=False)``,
``server_requantize(fused=False)``, ``TernaryCodec`` with
``fused_encode=False``) — codes are elementwise IEEE ops, per-leaf stats are
computed by the very same jnp expressions, and the w_q reduction follows the
canonical tile order defined in ``kernels.quantize_pack`` on both sides.
Property-tested in ``tests/test_encode.py``.

Ragged stacked leaves (per-layer size % 4 ≠ 0) pack bytes ACROSS layer
boundaries on the wire, which no per-layer staging can emit directly; the
kernel still does all the fp work and a cheap host pass re-aligns the 2-bit
codes across the boundaries (``_repack_ragged``) — so "one launch per
client update" holds unconditionally, with byte-identical wire output.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fttq
from repro.core.ternary import TernaryTensor, packed_nbytes
from repro.kernels.ops import use_interpret
from repro.kernels.quantize_pack import (
    BLOCK_S,
    LANES,
    quantize_pack_segments,
    quantize_pack_stacked,
    scale_from_moments,
    stage_encode,
    staged_rows,
)

Pytree = Any

_EPS = 1e-8


@dataclasses.dataclass(frozen=True)
class _Meta:
    """Static (hashable) per-leaf descriptor for the jitted group encode.

    mode: "payload" (trained w_q given, Δ from the threshold rule),
          "codec"   (w_q from moments, Δ from the threshold rule),
          "server"  (w_q from moments, fixed Δ = server_delta).
    """

    shape: tuple
    dtype: str
    mode: str
    rule: str = "mean"
    t_k: float = 0.7
    server_delta: float = 0.05
    has_wq: bool = False


def _n_elements(shape: tuple) -> int:
    return int(np.prod(shape)) if shape else 1


@functools.partial(jax.jit, static_argnames=("meta", "block_s", "interpret"))
def _encode_flat_group(
    leaves: tuple, meta: tuple, block_s: int, interpret: bool
) -> tuple[jax.Array, tuple]:
    """All single-segment leaves of one dtype → one fused kernel launch.

    Per-leaf denominators come from ONE batched |·|-max over the whole
    group's staging (one reduction per dtype group, not one per leaf): max
    is order-invariant and the staging's zero padding cannot move an
    abs-max, so each per-leaf slice reproduces the reference's
    ``jnp.max(jnp.abs(leaf))`` bit-exactly. The threshold MEAN stays a
    per-leaf reduction on purpose — fp summation order is part of the wire
    bytes, and batching it would break the byte-identity invariant.

    Returns (packed (S_total//4, LANES) uint8 — the concatenated wire byte
    streams, segment-aligned — and a per-leaf tuple of w_q scales, None
    where the caller supplies the trained factor)."""
    staged_parts, rows = [], []
    for leaf in leaves:
        staged, _ = stage_encode(leaf, block_s)
        staged_parts.append(staged)
        rows.append(staged.shape[0])
    staged_all = (staged_parts[0] if len(staged_parts) == 1
                  else jnp.concatenate(staged_parts, axis=0))
    row_max = jnp.max(jnp.abs(staged_all), axis=1)
    scal_parts, denoms = [], []
    off = 0
    for leaf, m, r in zip(leaves, meta, rows):
        denom = jnp.max(row_max[off:off + r]).astype(leaf.dtype) + _EPS
        off += r
        if m.mode == "server":
            delta = jnp.asarray(m.server_delta, leaf.dtype)
        else:
            # the same jnp expressions as the reference path, with the
            # batched denom substituted for scale_layer's internal max.
            delta = fttq.fttq_threshold(
                fttq.scale_layer(leaf, denom=denom), m.t_k, m.rule
            )
        g = r // block_s
        scal_parts.append(jnp.broadcast_to(
            jnp.stack([denom, delta]).astype(jnp.float32)[None, :], (g, 2)
        ))
        denoms.append(denom)
    scal_all = (scal_parts[0] if len(scal_parts) == 1
                else jnp.concatenate(scal_parts, axis=0))
    packed, moments = quantize_pack_segments(
        staged_all, scal_all, block_s=block_s, interpret=interpret
    )
    scales, off = [], 0
    for m, denom in zip(meta, denoms):
        g = staged_rows(_n_elements(m.shape), block_s) // block_s
        scales.append(
            None if m.has_wq
            else scale_from_moments(moments[off:off + g], denom).astype(m.dtype)
        )
        off += g
    return packed, tuple(scales)


@functools.partial(jax.jit, static_argnames=("meta", "block_s", "interpret"))
def _encode_stacked_leaf(
    leaf: jax.Array, meta: _Meta, block_s: int, interpret: bool
) -> tuple[jax.Array, jax.Array | None]:
    """One stacked (L, ...) scan leaf, its layers the segments of one
    launch: per-layer (denom, Δ) scalars, per-layer packed streams,
    per-layer w_q where the mode computes it. Ragged layer sizes are
    repacked host-side."""
    n_layers = leaf.shape[0]
    # ONE batched reduction for all layers' denominators (max is
    # order-invariant → bit-identical to the per-layer reference max).
    denoms = jnp.max(jnp.abs(leaf.reshape(n_layers, -1)), axis=1) + _EPS
    if meta.mode == "server":
        deltas = jnp.broadcast_to(
            jnp.asarray(meta.server_delta, leaf.dtype), (n_layers,)
        )
    else:
        deltas = jax.vmap(
            lambda t, d: fttq.fttq_threshold(
                fttq.scale_layer(t, denom=d), meta.t_k, meta.rule
            )
        )(leaf, denoms)
    packed, moments, _ = quantize_pack_stacked(
        leaf, denoms, deltas, block_s=block_s, interpret=interpret
    )
    if meta.has_wq:
        return packed, None
    scales = jnp.stack([
        scale_from_moments(moments[i], denoms[i]) for i in range(n_layers)
    ]).astype(leaf.dtype)
    return packed, scales


# --------------------------------------------------------------------------
# Batched leaf encode (the shared engine).
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _Item:
    leaf: jax.Array
    meta: _Meta
    wq: Any = None          # trained factor (payload mode) — passed through
    stacked: bool = False


def _repack_ragged(packed_np: np.ndarray, n_layers: int,
                   layer_n: int) -> np.ndarray:
    """Rebuild the flat wire stream of a RAGGED stacked leaf (layer size %
    4 ≠ 0) from the kernel's per-layer packed planes.

    The wire format packs the CONCATENATED per-layer codes 4-per-byte, so
    layer boundaries land mid-byte — no per-layer staging can emit those
    bytes directly. The kernel still does all the fp work (scale →
    threshold → ternarize → per-layer pack); this host pass just re-aligns
    the 2-bit codes across layer boundaries: unpack each layer's first
    ``layer_n`` codes, concatenate, pad the tail with code 1 (= value 0,
    ``pack2bit``'s padding), and repack. Byte-identical to packing the
    concatenated codes, i.e. to the reference wire stream."""
    per = packed_np.reshape(n_layers, -1)[:, : (layer_n + 3) // 4]
    codes = np.empty((n_layers, per.shape[1] * 4), dtype=np.uint8)
    for j in range(4):
        codes[:, j::4] = (per >> (2 * j)) & 3
    codes = codes[:, :layer_n].reshape(-1)
    pad = (-codes.size) % 4
    if pad:
        codes = np.concatenate([codes, np.ones(pad, dtype=np.uint8)])
    q = codes.reshape(-1, 4)
    return (q[:, 0] | (q[:, 1] << 2) | (q[:, 2] << 4)
            | (q[:, 3] << 6)).astype(np.uint8)


def _encode_items(
    items: Sequence[_Item], *, block_s: int | None = None,
    interpret: bool | None = None,
) -> list[TernaryTensor]:
    """Encode a batch of quantizable leaves; one flat-group launch per dtype
    plus one launch per stacked leaf, then ONE device→host transfer
    for every packed stream and kernel-computed w_q scale of the whole
    batch. Output order matches input."""
    bs = BLOCK_S if block_s is None else block_s
    interp = use_interpret(interpret)
    out: list[TernaryTensor | None] = [None] * len(items)

    # stacked leaves: per-layer segments
    stacked_res = [
        (i, *_encode_stacked_leaf(it.leaf, it.meta, bs, interp))
        for i, it in enumerate(items) if it.stacked
    ]

    # flat leaves: one launch per dtype group
    flat_ids = [i for i, it in enumerate(items) if not it.stacked]
    by_dtype: dict[str, list[int]] = {}
    for i in flat_ids:
        by_dtype.setdefault(items[i].meta.dtype, []).append(i)
    flat_res = []
    for ids in by_dtype.values():
        leaves = tuple(items[i].leaf for i in ids)
        meta = tuple(items[i].meta for i in ids)
        flat_res.append((ids, *_encode_flat_group(leaves, meta, bs, interp)))

    # ONE batched host sync for the whole update (the per-leaf np.asarray
    # calls this replaces each blocked on its own transfer).
    sp, ss, fp, fs = jax.device_get((
        [p for _, p, _ in stacked_res],
        [s for _, _, s in stacked_res],
        [p for _, p, _ in flat_res],
        [list(s) for _, _, s in flat_res],
    ))

    for (i, _, _), packed_np, scales in zip(stacked_res, sp, ss):
        it = items[i]
        layer_n = _n_elements(it.meta.shape[1:])
        if layer_n % 4 == 0:
            stream = np.concatenate(
                [packed_np[layer].reshape(-1)[: layer_n // 4]
                 for layer in range(it.leaf.shape[0])]
            )
        else:
            stream = _repack_ragged(packed_np, it.leaf.shape[0], layer_n)
        if it.meta.has_wq:
            wq = it.wq
        else:
            wq = scales.reshape(
                (it.leaf.shape[0],) + (1,) * (it.leaf.ndim - 1)
            )
        out[i] = TernaryTensor(
            packed=stream, w_q=wq, shape=it.meta.shape, dtype=it.meta.dtype
        )

    for (ids, _, _), packed_np, scales in zip(flat_res, fp, fs):
        flat_bytes = packed_np.reshape(-1)
        off_rows = 0
        for i, scale in zip(ids, scales):
            it = items[i]
            n = _n_elements(it.meta.shape)
            byte_off = (off_rows // 4) * LANES
            stream = flat_bytes[byte_off:byte_off + packed_nbytes(n)]
            wq = it.wq if it.meta.has_wq else scale
            out[i] = TernaryTensor(
                packed=stream, w_q=wq, shape=it.meta.shape, dtype=it.meta.dtype
            )
            off_rows += staged_rows(n, bs)
    return out  # type: ignore[return-value]


def _is_stacked(leaf, wq) -> bool:
    """Per-layer treatment mirrors the reference dispatch: ndim ≥ 3 with a
    broadcast-shaped per-layer factor tree."""
    return leaf.ndim >= 3 and hasattr(wq, "ndim") and wq.ndim == leaf.ndim


# --------------------------------------------------------------------------
# Public entry points (one per rewired call site).
# --------------------------------------------------------------------------


def client_payload_fused(
    params: Pytree, wq_tree: Pytree, cfg: fttq.FTTQConfig, *,
    block_s: int | None = None, interpret: bool | None = None,
) -> Pytree:
    """Fused ``core.tfedavg.client_update_payload``: trained w_q per leaf,
    whole update encoded in O(few) launches, byte-identical wire output."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    wqs = jax.tree_util.tree_flatten(wq_tree, is_leaf=lambda x: x is None)[0]
    out = list(leaves)
    items, idxs = [], []
    for i, (leaf, wq) in enumerate(zip(leaves, wqs)):
        if wq is None:
            continue
        stacked = _is_stacked(leaf, wq)
        meta = _Meta(
            shape=tuple(int(s) for s in leaf.shape), dtype=str(leaf.dtype),
            mode="payload", rule=cfg.threshold_rule, t_k=cfg.t_k, has_wq=True,
        )
        items.append(_Item(leaf=leaf, meta=meta, wq=wq, stacked=stacked))
        idxs.append(i)
    for i, t in zip(idxs, _encode_items(items, block_s=block_s,
                                        interpret=interpret)):
        out[i] = t
    return jax.tree_util.tree_unflatten(treedef, out)


def requantize_fused(
    global_params: Pytree, cfg: fttq.FTTQConfig, wq_tree: Pytree | None = None,
    *, block_s: int | None = None, interpret: bool | None = None,
) -> Pytree:
    """Fused ``core.tfedavg.server_requantize``: fixed Δ = server_delta on
    scaled weights, downstream scale from the same-pass moments."""
    if wq_tree is None:
        wq_tree = fttq.init_wq_tree(global_params, cfg)
    leaves, treedef = jax.tree_util.tree_flatten(global_params)
    wqs = jax.tree_util.tree_flatten(wq_tree, is_leaf=lambda x: x is None)[0]
    out = list(leaves)
    items, idxs = [], []
    for i, (leaf, wq) in enumerate(zip(leaves, wqs)):
        if wq is None:
            continue
        stacked = _is_stacked(leaf, wq)
        meta = _Meta(
            shape=tuple(int(s) for s in leaf.shape), dtype=str(leaf.dtype),
            mode="server", server_delta=cfg.server_delta, has_wq=False,
        )
        items.append(_Item(leaf=leaf, meta=meta, stacked=stacked))
        idxs.append(i)
    for i, t in zip(idxs, _encode_items(items, block_s=block_s,
                                        interpret=interpret)):
        out[i] = t
    return jax.tree_util.tree_unflatten(treedef, out)


def encode_codec_leaves_fused(
    leaves: Sequence[jax.Array], spec, *,
    block_s: int | None = None, interpret: bool | None = None,
) -> list[TernaryTensor]:
    """Fused ``TernaryCodec.encode_leaf`` over a BATCH of raw leaves (the
    ``compress_pytree`` pre-pass): whole-leaf scale regardless of ndim —
    exactly the codec reference — so every leaf is one segment and the batch
    is one launch per dtype."""
    cfg = spec.fttq
    items = [
        _Item(
            leaf=leaf,
            meta=_Meta(
                shape=tuple(int(s) for s in leaf.shape), dtype=str(leaf.dtype),
                mode="codec", rule=cfg.threshold_rule, t_k=cfg.t_k,
                has_wq=False,
            ),
        )
        for leaf in leaves
    ]
    return _encode_items(items, block_s=block_s, interpret=interpret)
