"""Multi-device fan-in: shard the CLIENT axis of the fused packed aggregator.

The streaming ``fed.aggregator`` batches up to ``chunk_c`` packed client
updates into one ``(C, R, LANES)`` uint8 tensor per kernel launch. At
million-client fan-in one device's HBM bandwidth becomes the ceiling, so
this module splits the C axis across a mesh with ``shard_map``: every
device runs ``kernels.aggregate.packed_weighted_sum`` over its client
shard (coefficients travel with their rows) and a single fp32 ``psum``
over the dense partials merges the shards — wire bytes never cross
devices un-aggregated, only one dense tree per device does (the ROADMAP's
"shard aggregation across devices for million-client fan-in").

``fanin_weighted_sum`` is the single entry point: mesh-less (or a C that
does not divide the axis) degrades to one kernel launch on the default
device; every (shape, mesh) signature is compiled exactly once through an
``lru_cache`` of jitted closures, so the trace count is inspectable
(``fanin_trace_count``) and bounded by the aggregator's bucket set.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.aggregate import BLOCK_ROWS, packed_weighted_sum
from repro.kernels.ops import use_interpret
from repro.kernels.vote import packed_vote_counts


def _fanin_axis(mesh: Mesh) -> str:
    """The mesh axis the client dimension shards over ("data" when present
    — clients are the data-parallel resource — else the first axis)."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


@functools.lru_cache(maxsize=None)
def _build(c: int, rows: int, block_rows: int, interpret: bool,
           mesh: Mesh | None, axis: str | None):
    if mesh is not None:
        n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if mesh is None or n_shards == 1 or c % n_shards:
        @jax.jit
        def run(stacked, coeffs):
            return packed_weighted_sum(
                stacked, coeffs, block_rows=block_rows, interpret=interpret
            )
        return run

    def shard(stacked, coeffs):
        part = packed_weighted_sum(
            stacked, coeffs, block_rows=block_rows, interpret=interpret
        )
        return jax.lax.psum(part, axis)

    # check_vma=False: pallas_call has no replication rule; the psum above
    # establishes the replicated output explicitly.
    return jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _build_vote(c: int, rows: int, block_rows: int, interpret: bool,
                mesh: Mesh | None, axis: str | None):
    if mesh is not None:
        n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if mesh is None or n_shards == 1 or c % n_shards:
        @jax.jit
        def run(stacked, coeffs):
            return packed_vote_counts(
                stacked, coeffs, block_rows=block_rows, interpret=interpret
            )
        return run

    def shard(stacked, coeffs):
        part = packed_vote_counts(
            stacked, coeffs, block_rows=block_rows, interpret=interpret
        )
        # vote masses are plain weighted sums over the client axis, so the
        # same psum merge as the mean path applies.
        return jax.lax.psum(part, axis)

    return jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False,
    ))


def fanin_vote_counts(
    stacked,
    coeffs,
    *,
    mesh: Mesh | None = None,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Weighted −1/+1 vote masses per coordinate, C-sharded over ``mesh``.

    Same staging contract as ``fanin_weighted_sum``; returns
    (2, 4·R·LANES) fp32 [minus_mass, plus_mass], replicated.
    """
    interp = use_interpret(interpret)
    c, rows, _ = stacked.shape
    axis = _fanin_axis(mesh) if mesh is not None else None
    fn = _build_vote(c, rows, block_rows, interp, mesh, axis)
    return fn(stacked, coeffs)


def fanin_weighted_sum(
    stacked,
    coeffs,
    *,
    mesh: Mesh | None = None,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Σ_c coeffs[c]·unpack(stacked[c]), C-sharded over ``mesh`` when given.

    stacked: (C, R, LANES) uint8 flat-packed 2-bit codes; coeffs: (C,) f32;
    host (numpy) arrays are moved to the device by the jitted launch itself.
    Returns the flat fp32 weighted sum (length 4·R·LANES), replicated.
    """
    interp = use_interpret(interpret)
    c, rows, _ = stacked.shape
    axis = _fanin_axis(mesh) if mesh is not None else None
    fn = _build(c, rows, block_rows, interp, mesh, axis)
    return fn(stacked, coeffs)


def fanin_trace_count() -> int:
    """Number of distinct compiled fan-in signatures this process has built
    — the aggregator's bucketing keeps this bounded by the bucket set."""
    return _build.cache_info().currsize
