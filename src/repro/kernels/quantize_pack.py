"""Pallas TPU kernel: fused one-pass quantize→pack for client egress.

The paper's upstream step (§III.B Algorithm 2) ships 2-bit ternary codes
every round, so the encode side of the wire must be as cheap as the fan-in
side (PR 3): the per-leaf jnp pipeline (scale → threshold → ternarize →
pack) costs ~5 HBM passes of fp32 per weight tensor. This kernel fuses the
whole elementwise chain: fp32/bf16 weights in, WIRE-layout packed uint8
codes out — one HBM read, one ~1/16-size write — and emits the per-tile
partial sums the trained-scale w_q needs (Σ masked |θ_s| and the selected
count) from the same pass, so no extra reduction over the weights runs.

Staging layout (``stage_encode``): the flat leaf, zero-padded, viewed as
(S, LANES) rows of 128 consecutive elements — a reshape, no transpose. The
wire packs 4 CONSECUTIVE flat elements per byte (``core.ternary.pack2bit``),
so wire row r' (128 bytes) draws on staged rows 4r'..4r'+3: byte
``32a + t`` of it holds the codes at lanes 4t..4t+3 of row 4r'+a. That
stride-4 lane compaction runs on the MXU: the kernel multiplies each of
the 4 row groups by a constant 0/4ʲ selection matrix (``pack_matrix``) and
sums — small exact integers in bf16 with fp32 accumulation — so the packed
output tile IS the wire byte stream in order (flatten, slice to
``packed_nbytes(n)``, done). An interleaving transpose in the staging
instead would make XLA materialize an (S/4, 128, 4) intermediate, which a
TPU pads 32× in its (8, 128) tiling.

Scalars: the whole per-block (denom, Δ) table sits in SMEM and grid step
``i`` reads its own row, so ONE launch encodes many segments (leaves /
stacked-scan layers) back to back — the batched tree encoder in
``core.encode`` concatenates per-segment staging and drives the whole
client update through a single kernel call. The table and the per-tile
moments are whole flat SMEM arrays indexed by ``pl.program_id``: Mosaic
refuses per-block ``(1, 2)`` SMEM tiles of a ``(G, 2)`` array once G > 1
(a block's last two dims must tile (8, 128) or span the array).

Bit-exactness contract: codes are comparisons and elementwise IEEE ops —
identical to the jnp reference by construction. The w_q numerator is a
float SUM, whose value depends on reduction order, so the canonical order
is defined HERE: per-(block_s, LANES)-tile partials in tile order, summed
by one final (G,) reduction. ``moments_ref`` is the pure-jnp oracle with
the identical structure (``lax.map`` over the same tiles); the reference
encode paths in ``core``/``comm`` compute w_q through it, which is what
makes fused and reference wire buffers byte-identical (property-tested in
``tests/test_encode.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.pack2bit import pad_to_packable

LANES = 128
BLOCK_S = 256   # staged sublane rows per grid step: (256, 128) fp32 = 128 KiB
                # in + 8 KiB packed out + 128 KiB bf16 pack matrix — well
                # under VMEM


def staged_rows(n_elements: int, block_s: int = BLOCK_S) -> int:
    """Sublane rows of the staging buffer for a leaf of ``n_elements``:
    ⌈n / LANES⌉ rounded up to a multiple of ``block_s`` (tiles never
    straddle segments)."""
    rows = pl.cdiv(max(n_elements, 1), LANES)
    return int(pl.cdiv(rows, block_s) * block_s)


def stage_encode(x: jax.Array, block_s: int = BLOCK_S) -> tuple[jax.Array, int]:
    """Flatten + zero-pad one leaf into the kernel's staging.

    Reuses ``pack2bit.pad_to_packable`` for the 4·LANES padding contract
    (zero padding = wire code 1 = value 0), then pads rows to a multiple of
    ``block_s``. Returns (staged (S, LANES), n).
    """
    tiled, n = pad_to_packable(x.reshape(-1), lanes=LANES)
    flat = tiled.reshape(-1)
    chunk = block_s * LANES
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, LANES), n


def pack_matrix() -> np.ndarray:
    """(4, LANES, LANES) selection: ``W[a, 4t + j, 32a + t] = 4ʲ``, so
    Σ_a codes[a::4] @ W[a] puts the 4 codes at lanes 4t..4t+3 of staged row
    4r'+a into byte 32a + t of wire row r'."""
    w = np.zeros((4, LANES, LANES), np.float32)
    t = np.arange(LANES // 4)
    for a in range(4):
        for j in range(4):
            w[a, 4 * t + j, (LANES // 4) * a + t] = 4 ** j
    return w


def _kernel(s_ref, w_ref, x_ref, p_ref, m_ref):
    """One (block_s, LANES) staged tile → (block_s//4, LANES) wire bytes +
    its two partial moments, all in one VMEM round trip. ``s_ref`` and
    ``m_ref`` are the whole flat (2G,) SMEM tables."""
    i = pl.program_id(0)
    denom = s_ref[2 * i]
    delta = s_ref[2 * i + 1]
    x = x_ref[...]
    xs = x / denom.astype(x.dtype)          # g(θ): same DIVISION as scale_layer
    d = delta.astype(x.dtype)
    pos = (xs > d).astype(jnp.int32)
    neg = (xs < -d).astype(jnp.int32)       # |xs| > d ⟺ pos ∨ neg for d ≥ 0
    c = 1 + pos - neg                       # wire code = I_t + 1 ∈ {0, 1, 2}
    bs, lanes = x.shape
    c4 = c.reshape(bs // 4, 4, lanes)       # row group a of every wire row
    packed = sum(
        jnp.dot(c4[:, a].astype(jnp.float32).astype(jnp.bfloat16), w_ref[a],
                preferred_element_type=jnp.float32)
        for a in range(4)
    )                                       # exact: integers ≤ 170
    p_ref[...] = packed.astype(jnp.int32).astype(jnp.uint8)
    mask = (pos + neg) > 0
    a = jnp.abs(xs).astype(jnp.float32)
    m_ref[2 * i] = jnp.sum(jnp.where(mask, a, 0.0))       # Σ |θ_s| over selected
    m_ref[2 * i + 1] = jnp.sum(mask.astype(jnp.float32))  # count (exact ≤ 2²⁴)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def quantize_pack_segments(
    staged: jax.Array,
    scalars: jax.Array,
    *,
    block_s: int = BLOCK_S,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused ternarize+pack over a multi-segment staging buffer.

    staged:  (S, LANES) float staging (``stage_encode`` layout, possibly a
             concatenation of many segments), S % block_s == 0.
    scalars: (S // block_s, 2) fp32 — per-BLOCK (denom, Δ); every block of
             one segment carries that segment's row.
    Returns (packed (S//4, LANES) uint8 wire bytes, moments (G, 2) fp32 —
    per-tile [Σ masked |θ_s|, selected count]).
    """
    s, lanes = staged.shape
    assert lanes == LANES, f"lane dim must be {LANES}, got {lanes}"
    assert s % block_s == 0, f"rows {s} not a multiple of block_s {block_s}"
    g = s // block_s
    packed, moments = pl.pallas_call(
        _kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((4, LANES, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec((block_s, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s // 4, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s // 4, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((2 * g,), jnp.float32),
        ],
        interpret=interpret,
    )(scalars.astype(jnp.float32).reshape(-1),
      jnp.asarray(pack_matrix(), jnp.bfloat16), staged)
    return packed, moments.reshape(g, 2)


def quantize_pack(
    theta: jax.Array,
    denom: jax.Array,
    delta: jax.Array,
    *,
    block_s: int = BLOCK_S,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, int]:
    """Single-segment convenience: one leaf → (packed bytes (S//4, LANES),
    moments (G, 2), n_elements). Flatten + slice ``[:packed_nbytes(n)]`` of
    the flattened output to get the exact wire byte stream."""
    staged, n = stage_encode(theta, block_s)
    g = staged.shape[0] // block_s
    scal = jnp.broadcast_to(
        jnp.stack([denom, delta]).astype(jnp.float32)[None, :], (g, 2)
    )
    packed, moments = quantize_pack_segments(
        staged, scal, block_s=block_s, interpret=interpret
    )
    return packed, moments, n


def quantize_pack_stacked(
    theta: jax.Array,
    denoms: jax.Array,
    deltas: jax.Array,
    *,
    block_s: int = BLOCK_S,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, int]:
    """Stacked scan leaves: (L, ...) weights with per-layer (denom, Δ) →
    (L, rows//4, LANES) per-layer wire bytes + (L, G, 2) moments. Each layer
    stages independently and the L stagings run as L segments of ONE
    ``quantize_pack_segments`` launch, so concatenating the per-layer
    streams reproduces the flat wire stream only when the layer size is a
    multiple of 4 (the caller checks; ragged stacks are repacked by the
    caller). Bit-exact with L independent ``quantize_pack`` calls."""
    staged = jax.vmap(lambda layer: stage_encode(layer, block_s)[0])(theta)
    n_layers, rows, _ = staged.shape
    g = rows // block_s
    scal = jnp.repeat(
        jnp.stack([denoms, deltas], axis=1).astype(jnp.float32), g, axis=0
    )
    packed, moments = quantize_pack_segments(
        staged.reshape(-1, LANES), scal, block_s=block_s, interpret=interpret
    )
    n_layer = int(np.prod(theta.shape[1:])) if theta.ndim > 1 else 1
    return (packed.reshape(n_layers, rows // 4, LANES),
            moments.reshape(n_layers, g, 2), n_layer)


# --------------------------------------------------------------------------
# Pure-jnp oracles (the canonical reduction the reference paths share).
# --------------------------------------------------------------------------


def moments_ref(
    x: jax.Array, denom: jax.Array, delta: jax.Array, *, block_s: int = BLOCK_S
) -> jax.Array:
    """Canonical per-tile (Σ masked |θ_s|, count) partials — bit-identical
    to the kernel's SMEM moment outputs: the same (block_s, LANES) tiles in
    the same order, reduced by an identically-shaped op per tile."""
    staged, _ = stage_encode(x, block_s)
    tiles = staged.reshape(-1, block_s, LANES)

    def tile_moments(t):
        xs = t / denom.astype(t.dtype)
        d = delta.astype(t.dtype)
        mask = (xs > d) | (xs < -d)
        a = jnp.abs(xs).astype(jnp.float32)
        return jnp.stack(
            [jnp.sum(jnp.where(mask, a, 0.0)), jnp.sum(mask.astype(jnp.float32))]
        )

    return jax.lax.map(tile_moments, tiles)


def scale_from_moments(moments: jax.Array, denom: jax.Array) -> jax.Array:
    """The Prop-4.1 trained scale from canonical moments, in ORIGINAL
    units: (Σ masked |θ_s| / (count + 1e-8)) · denom. Shared by the fused
    wrapper and the jnp reference so both produce the same fp bits."""
    num = jnp.sum(moments[:, 0])
    den = jnp.sum(moments[:, 1].astype(jnp.int32))
    return num / (den + 1e-8) * denom


def quantize_pack_ref(
    x: jax.Array, denom: jax.Array, delta: jax.Array
) -> jax.Array:
    """Wire-byte oracle: ternarize then pack 4 consecutive flat codes per
    byte (``core.ternary.pack2bit`` layout, code-1 padding)."""
    xs = x.reshape(-1) / denom.astype(x.dtype)
    d = delta.astype(x.dtype)
    codes = 1 + (xs > d).astype(jnp.int32) - (xs < -d).astype(jnp.int32)
    pad = (-codes.shape[0]) % 4
    if pad:
        codes = jnp.concatenate([codes, jnp.ones((pad,), jnp.int32)])
    c = codes.reshape(-1, 4)
    return (c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4) | (c[:, 3] << 6)).astype(
        jnp.uint8
    )
