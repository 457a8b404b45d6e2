"""Byte-level wire codec for federated update payloads.

An *update* is a pytree of leaves (raw arrays and/or registered wire leaves:
``TernaryTensor``, ``DowncastTensor``, ``TopKTensor``) as produced by
``core.tfedavg.client_update_payload`` / ``server_requantize`` /
``core.compression.compress_pytree``. ``encode_update`` serializes it into
one self-describing buffer; ``decode_update`` rebuilds the pytree
bit-exactly. All byte accounting in the repo is ``len(encode_update(tree))``
— measured from the actual buffer, never estimated.

Buffer layout (all little-endian):

    HEADER (24 B):
      magic      4s   b"TFW1"  (format family; the version field increments)
      version    u16  lowest version able to carry the payload's records
      flags      u16  reserved (0)
      n_records  u32  number of leaf records
      crc32      u32  zlib.crc32 of the record section
      body_len   u64  length of the record section in bytes

    RECORD (one per pytree leaf, in tree_flatten order):
      path_len   u16  + path bytes (utf-8; entries joined by "\\x1f",
                        each entry "d:<key>" for dict keys or
                        "i:<index>" for sequence indices)
      kind       u8   dispatched through the record registry:
        0 RAW      (v1) dtype/ndim/dims, data_len u64 + raw array bytes
        1 TERNARY  (v1) a ``TernaryTensor``: logical dtype/ndim/dims, scale
                   array (dtype/ndim/dims + bytes), packed_len u64 + packed
                   2-bit codes (4 codes/byte, ``kernels.pack2bit`` layout)
        2 DOWNCAST (v2) a ``DowncastTensor``: orig dtype string + the
                   downcast payload as a RAW-style array
        3 TOPK     (v2) a ``TopKTensor``: logical dtype/ndim/dims + indices
                   array (uint32) + values array, both RAW-style
                   (decode-only since v3 — encoders emit TOPK_DELTA)
        4 TOPK_DELTA (v3) a ``TopKTensor`` with DELTA-VARINT indices: the
                   sorted uint32 flat indices ship as LEB128 varints (first
                   index absolute, then strictly-positive gaps) + the values
                   array RAW-style — ~4× fewer index bytes at 10% density

Record kinds are a REGISTRY (``register_record``): each entry binds a kind
byte to a wire-leaf class and its pack/unpack functions, plus the minimum
wire version that may carry it. ``WIRE_VERSION`` is 3; encoders stamp the
LOWEST version whose record set covers the payload (RAW/TERNARY-only
buffers stay v1 so deployed v1-only readers keep working; downcast bumps to
v2, delta-top-k to v3), and decoders accept every ``SUPPORTED_VERSIONS``
buffer — stored v1/v2 checkpoints and captures stay readable forever.

``encode_update`` is STREAMING: a size pre-pass walks the records
(``WireRecord.prepare`` returns each body's exact size plus a writer), one
buffer of the final length is allocated, and every record writes its header
fields and array payloads straight into it (numpy-view memcpy, no
intermediate per-record ``bytes``) — serializing a ResNet payload is one
allocation instead of O(records) concatenations. Records registered with
only the legacy ``pack`` still work: a fallback ``prepare`` materializes
their body once and copies it in.

Decoding parses the record section IN PLACE: one read-only ``memoryview``
of the caller's buffer, CRC'd once, fields unpacked from it at a cursor and
array payloads taken as numpy views of it. Only a mutable input buffer is
copied (once, before the CRC), and ``decode_update`` copies each array into
a leaf of its own; the counter ``wire.copied_bytes`` adds up both.

The CRC covers the whole record section; ``decode_update`` raises
``WireError`` on magic/version/CRC mismatch, truncation, or any malformed
record — a corrupted or torn transfer never silently yields wrong weights
and never escapes as a non-``WireError`` exception.
"""

from __future__ import annotations

import dataclasses
import struct
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.compression import (
    KIND_DOWNCAST,
    KIND_RAW,
    KIND_TERNARY,
    KIND_TOPK,
    KIND_TOPK_DELTA,
    DowncastTensor,
    TopKTensor,
    wire_leaf_types,
)
from repro.core.ternary import TernaryTensor

Pytree = Any

WIRE_MAGIC = b"TFW1"
WIRE_VERSION = 3
SUPPORTED_VERSIONS = (1, 2, 3)

_HEADER = struct.Struct("<4sHHIIQ")   # magic, version, flags, n_records, crc, body_len
_PATH_SEP = "\x1f"


class WireError(ValueError):
    """Malformed / corrupted / incompatible wire buffer."""


# --------------------------------------------------------------------------
# Low-level field packers.
# --------------------------------------------------------------------------


def _np(leaf) -> np.ndarray:
    return np.asarray(leaf)


# dtype-name prefixes are a tiny closed set but ``np.dtype.name`` is a
# surprisingly slow computed property — cache the encoded field per dtype
# (and per name string for the string-keyed callers).
_DTYPE_FIELD_CACHE: dict = {}


def _dtype_field(name: str) -> bytes:
    field = _DTYPE_FIELD_CACHE.get(name)
    if field is None:
        dt = name.encode("ascii")
        field = struct.pack("<B", len(dt)) + dt
        _DTYPE_FIELD_CACHE[name] = field
    return field


def _pack_array_meta(arr: np.ndarray) -> bytes:
    field = _DTYPE_FIELD_CACHE.get(arr.dtype)
    if field is None:
        field = _dtype_field(arr.dtype.name)
        _DTYPE_FIELD_CACHE[arr.dtype] = field
    return field + _pack_shape(arr.shape)


def _pack_shape(shape: tuple) -> bytes:
    if not shape:
        return b"\x00"
    return struct.pack(f"<B{len(shape)}I", len(shape), *shape)


def _pack_meta(dtype: str, shape: tuple) -> bytes:
    return _dtype_field(dtype) + _pack_shape(shape)


def _pack_arr(arr: np.ndarray) -> bytes:
    """RAW-style array field: meta + u64 length + raw little-endian bytes."""
    return b"".join(
        [_pack_array_meta(arr), struct.pack("<Q", arr.nbytes), arr.tobytes()]
    )


# --------------------------------------------------------------------------
# Streaming record writers (the encode_update fast path).
# --------------------------------------------------------------------------


# One record body, measured: (exact byte size, emitter). The emitter is
# either the body itself as ``bytes`` (small records — one slice assign in
# the write loop, no closure) or a writer callable that memcpys large array
# payloads into the preallocated buffer and returns the new offset. A plain
# tuple, not a dataclass: encode_update builds one per record and
# object-construction overhead is measurable at that rate.
_Prepared = tuple  # (int, bytes | Callable[[memoryview, int], int])


def _write_array_bytes(view: memoryview, off: int, arr: np.ndarray) -> int:
    """memcpy a C-contiguous array's raw little-endian bytes into the
    buffer — no intermediate ``tobytes`` allocation."""
    end = off + arr.nbytes
    if arr.nbytes:
        view[off:end] = arr.reshape(-1).view(np.uint8).data
    return end


def _contig(leaf) -> np.ndarray:
    arr = _np(leaf)
    # NOT np.ascontiguousarray unconditionally: it promotes 0-d to 1-d,
    # which would corrupt scalar w_q metadata on the wire.
    return arr if arr.flags["C_CONTIGUOUS"] else np.ascontiguousarray(arr)


# payloads at or below this fold into the record's head bytes at prepare
# time: for the many tiny fields (scalar w_q, biases) one small ``tobytes``
# beats the ~4-object numpy-view chain per array; large payloads (packed
# code streams, fp32 weights) keep the zero-copy memcpy into the buffer.
_INLINE_BYTES = 4096


def _head_writer(head: bytes, *arrays: np.ndarray) -> _Prepared:
    """Record body = fixed head bytes followed by raw array payloads."""
    while arrays and arrays[0].nbytes <= _INLINE_BYTES:
        head += arrays[0].tobytes()
        arrays = arrays[1:]
    size = len(head) + sum(a.nbytes for a in arrays)
    if not arrays:
        return (size, head)   # fully inlined: body IS the bytes

    def write(view: memoryview, off: int) -> int:
        end = off + len(head)
        view[off:end] = head
        for a in arrays:
            end = _write_array_bytes(view, end, a)
        return end

    return (size, write)


def _raw_prepare(leaf) -> _Prepared:
    arr = _contig(leaf)
    head = _pack_array_meta(arr) + struct.pack("<Q", arr.nbytes)
    return _head_writer(head, arr)


def _ternary_prepare(t: TernaryTensor) -> _Prepared:
    scale = _contig(t.w_q)
    packed = _contig(t.packed)
    if packed.dtype != np.uint8:
        raise WireError(f"TernaryTensor.packed must be uint8, got {packed.dtype}")
    head = _pack_meta(str(t.dtype), tuple(int(s) for s in t.shape)) \
        + _pack_array_meta(scale)
    mid = struct.pack("<Q", packed.size)
    if scale.nbytes <= _INLINE_BYTES:   # scalar / per-layer scales: tiny
        return _head_writer(head + scale.tobytes() + mid, packed)
    size = len(head) + scale.nbytes + len(mid) + packed.size

    def write(view: memoryview, off: int) -> int:
        end = off + len(head)
        view[off:end] = head
        end = _write_array_bytes(view, end, scale)
        view[end:end + len(mid)] = mid
        return _write_array_bytes(view, end + len(mid), packed)

    return (size, write)


def _downcast_prepare(t: "DowncastTensor") -> _Prepared:
    arr = _contig(t.data)
    dt = str(t.orig_dtype).encode("ascii")
    head = struct.pack("<B", len(dt)) + dt \
        + _pack_array_meta(arr) + struct.pack("<Q", arr.nbytes)
    return _head_writer(head, arr)


def _topk_delta_prepare(t: "TopKTensor") -> _Prepared:
    idx = _np(t.indices)
    if idx.dtype != np.uint32:
        raise WireError(f"TopKTensor.indices must be uint32, got {idx.dtype}")
    stream = _varint_pack(idx)
    values = _contig(t.values)
    head = _pack_meta(str(t.dtype), tuple(int(s) for s in t.shape)) \
        + struct.pack("<I", idx.size) + struct.pack("<Q", len(stream)) + stream \
        + _pack_array_meta(values) + struct.pack("<Q", values.nbytes)
    return _head_writer(head, values)


_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


class _Reader:
    """A cursor over a record section, parsed in place.

    ``buf`` is a read-only ``memoryview`` of the caller's blob (see
    ``_check_header``): scalar fields are unpacked from it at the cursor and
    array payloads are numpy views of it, so parsing copies no payload. In
    zero-copy mode (the streaming aggregator's ingest) those views are the
    leaves: read-only numpy arrays aliasing the caller's immutable ``bytes``,
    no device transfer. Otherwise each array is copied into a jax array of
    its own, so no leaf aliases the blob or keeps a whole upload alive."""

    def __init__(self, buf: memoryview, zero_copy: bool = False):
        self.buf = buf
        self.pos = 0
        self.zero_copy = zero_copy

    def skip(self, n: int) -> int:
        """Bounds-check the next ``n`` bytes; return their offset and move
        the cursor past them."""
        pos = self.pos
        if n < 0 or pos + n > len(self.buf):
            raise WireError(
                f"truncated wire buffer: need {n} bytes at offset {pos}, "
                f"have {len(self.buf) - pos}"
            )
        self.pos = pos + n
        return pos

    def take(self, n: int) -> memoryview:
        pos = self.skip(n)
        return self.buf[pos:pos + n]

    def text(self, n: int, encoding: str = "ascii") -> str:
        return str(self.take(n), encoding)

    def u8(self) -> int:
        return self.buf[self.skip(1)]

    def u16(self) -> int:
        return _U16.unpack_from(self.buf, self.skip(2))[0]

    def u32(self) -> int:
        return _U32.unpack_from(self.buf, self.skip(4))[0]

    def u64(self) -> int:
        return _U64.unpack_from(self.buf, self.skip(8))[0]

    def meta(self) -> tuple[str, tuple]:
        dt = self.text(self.u8())
        ndim = self.u8()
        if not ndim:
            return dt, ()
        return dt, struct.unpack_from(f"<{ndim}I", self.buf, self.skip(4 * ndim))

    def array(self, dtype: np.dtype, n: int, shape: tuple):
        """The next ``n`` items of ``dtype`` as an array of ``shape``: the
        view itself in zero-copy mode, else a jax array of its own."""
        a = np.frombuffer(self.buf, dtype, n, self.skip(n * dtype.itemsize))
        a = a.reshape(shape)
        if self.zero_copy:
            return a
        obs.count("wire.copied_bytes", a.nbytes)
        return jnp.array(a)


def _resolve_dtype(dtype: str) -> np.dtype:
    try:
        return np.dtype(jnp.dtype(dtype))
    except TypeError as e:
        raise WireError(f"unknown dtype {dtype!r} in wire record") from e


def _decode_array(r: _Reader) -> jax.Array:
    dtype, shape = r.meta()
    nbytes = r.u64()
    np_dt = _resolve_dtype(dtype)
    n = int(np.prod(shape)) if shape else 1
    if nbytes != n * np_dt.itemsize:
        raise WireError(
            f"record data length {nbytes} != {n}×{np_dt.itemsize} "
            f"for dtype={dtype} shape={shape}"
        )
    return r.array(np_dt, n, shape)


# --------------------------------------------------------------------------
# Record bodies, one pair of pack/unpack per wire kind.
# --------------------------------------------------------------------------


def _raw_body(leaf) -> bytes:
    return _pack_arr(_np(leaf))


def _ternary_body(t: TernaryTensor) -> bytes:
    scale = _np(t.w_q)
    packed = _np(t.packed)
    if packed.dtype != np.uint8:
        raise WireError(f"TernaryTensor.packed must be uint8, got {packed.dtype}")
    parts = [
        _pack_meta(str(t.dtype), tuple(int(s) for s in t.shape)),
        _pack_array_meta(scale),
        scale.tobytes(),
        struct.pack("<Q", packed.size),
        packed.tobytes(),
    ]
    return b"".join(parts)


def _decode_ternary_body(r: _Reader) -> TernaryTensor:
    dtype, shape = r.meta()
    s_dtype, s_shape = r.meta()
    s_np = _resolve_dtype(s_dtype)
    s_n = int(np.prod(s_shape)) if s_shape else 1
    scale = r.array(s_np, s_n, s_shape)
    n_packed = r.u64()
    n = int(np.prod(shape)) if shape else 1
    if n_packed != (n + 3) // 4:
        raise WireError(
            f"packed size {n_packed} inconsistent with logical shape {shape}"
        )
    packed = r.array(np.dtype(np.uint8), n_packed, (n_packed,))
    return TernaryTensor(packed=packed, w_q=scale, shape=tuple(shape), dtype=dtype)


def _downcast_body(t: DowncastTensor) -> bytes:
    dt = str(t.orig_dtype).encode("ascii")
    return b"".join([struct.pack("<B", len(dt)), dt, _pack_arr(_np(t.data))])


def _decode_downcast_body(r: _Reader) -> DowncastTensor:
    orig = r.text(r.u8())
    _resolve_dtype(orig)  # validate before it reaches restore()
    return DowncastTensor(data=_decode_array(r), orig_dtype=orig)


def _topk_body(t: TopKTensor) -> bytes:
    idx = _np(t.indices)
    if idx.dtype != np.uint32:
        raise WireError(f"TopKTensor.indices must be uint32, got {idx.dtype}")
    parts = [
        _pack_meta(str(t.dtype), tuple(int(s) for s in t.shape)),
        _pack_arr(idx),
        _pack_arr(_np(t.values)),
    ]
    return b"".join(parts)


def _decode_topk_body(r: _Reader) -> TopKTensor:
    dtype, shape = r.meta()
    _resolve_dtype(dtype)
    indices = _decode_array(r)
    values = _decode_array(r)
    n = int(np.prod(shape)) if shape else 1
    if indices.shape != values.shape or indices.ndim != 1:
        raise WireError(
            f"topk indices/values shapes differ: {indices.shape} vs {values.shape}"
        )
    if indices.size and int(jnp.max(indices)) >= n:
        raise WireError(f"topk index out of range for logical shape {shape}")
    return TopKTensor(
        indices=indices, values=values, shape=tuple(shape), dtype=dtype
    )


# --------------------------------------------------------------------------
# TOPK_DELTA (v3): sorted u32 indices as LEB128 varint deltas.
# --------------------------------------------------------------------------


def _varint_pack(values: np.ndarray) -> bytes:
    """Ascending uint32 indices → LEB128 stream: first absolute, then gaps.

    Strictly ascending is the TopKTensor contract (unique sorted top-k
    indices) — violated input is rejected HERE rather than producing a
    stream no decoder will accept. Fully vectorized (server encode path).
    """
    if values.size == 0:
        return b""
    v = values.astype(np.uint64)
    if v.size > 1 and not np.all(values[1:] > values[:-1]):
        raise WireError("TopKTensor indices must be strictly ascending")
    d = np.empty(v.shape, np.uint64)
    d[0] = v[0]
    d[1:] = v[1:] - v[:-1]
    nbytes = np.ones(d.shape, np.int64)          # LEB128 length per gap
    for j in range(1, 6):                        # u32 gaps need ≤ 5 bytes
        nbytes += (d >> np.uint64(7 * j)) > 0
    offsets = np.concatenate([[0], np.cumsum(nbytes)])
    out = np.zeros(int(offsets[-1]), np.uint8)
    for j in range(int(nbytes.max())):
        mask = nbytes > j
        byte = ((d[mask] >> np.uint64(7 * j)) & np.uint64(0x7F)).astype(np.uint8)
        cont = (nbytes[mask] - 1 > j).astype(np.uint8) << 7
        out[offsets[:-1][mask] + j] = byte | cont
    return out.tobytes()


def _varint_unpack(stream: bytes, k: int) -> np.ndarray:
    """LEB128 stream → k uint64 values (the gap sequence). Vectorized: the
    continuation bits delimit groups; ``np.add.reduceat`` folds each group's
    7-bit limbs — no per-index Python loop on the server ingest path."""
    b = np.frombuffer(stream, np.uint8)
    if k == 0:
        if b.size:
            raise WireError(f"{b.size} trailing bytes in empty varint stream")
        return np.zeros((0,), np.uint64)
    is_end = (b & 0x80) == 0
    if b.size == 0 or not is_end[-1]:
        raise WireError("unterminated varint in topk delta stream")
    if int(is_end.sum()) != k:
        raise WireError(
            f"varint stream carries {int(is_end.sum())} values, expected {k}"
        )
    starts = np.flatnonzero(np.concatenate([[True], is_end[:-1]]))
    gid = np.cumsum(np.concatenate([[0], is_end[:-1].astype(np.int64)]))
    pos = np.arange(b.size) - starts[gid]        # limb index within varint
    if int(pos.max()) > 4:                       # u32 gaps need ≤ 5 limbs
        raise WireError("varint overflows uint32 index range")
    limbs = (b & 0x7F).astype(np.uint64) << (7 * pos).astype(np.uint64)
    return np.add.reduceat(limbs, starts)


def _topk_delta_body(t: TopKTensor) -> bytes:
    idx = _np(t.indices)
    if idx.dtype != np.uint32:
        raise WireError(f"TopKTensor.indices must be uint32, got {idx.dtype}")
    stream = _varint_pack(idx)
    parts = [
        _pack_meta(str(t.dtype), tuple(int(s) for s in t.shape)),
        struct.pack("<I", idx.size),
        struct.pack("<Q", len(stream)),
        stream,
        _pack_arr(_np(t.values)),
    ]
    return b"".join(parts)


def _decode_topk_delta_body(r: _Reader) -> TopKTensor:
    dtype, shape = r.meta()
    _resolve_dtype(dtype)
    k = r.u32()
    stream = r.take(r.u64())
    n = int(np.prod(shape)) if shape else 1
    gaps = _varint_unpack(stream, k)
    if gaps.size > 1 and not np.all(gaps[1:] > 0):
        raise WireError("topk delta stream not strictly ascending")
    idx64 = np.cumsum(gaps)
    if idx64.size and (int(idx64[-1]) >= n or int(idx64[-1]) > 0xFFFFFFFF):
        # the explicit u32 bound matters when n itself exceeds u32 (huge
        # multi-dim leaves): astype(uint32) must never silently wrap.
        raise WireError(
            f"topk index {int(idx64[-1])} out of range for shape {shape}"
        )
    idx = idx64.astype(np.uint32)
    values = _decode_array(r)
    if _np(values).ndim != 1 or _np(values).shape != (k,):
        raise WireError(
            f"topk values shape {_np(values).shape} != index count {k}"
        )
    return TopKTensor(
        indices=idx if r.zero_copy else jnp.asarray(idx), values=values,
        shape=tuple(shape), dtype=dtype,
    )


# --------------------------------------------------------------------------
# The record registry: kind byte ↔ wire-leaf class ↔ pack/unpack.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WireRecord:
    kind: int
    name: str
    leaf_type: type | None          # None = RAW fallback for plain arrays
    pack: Callable[[Any], bytes]
    unpack: Callable[[_Reader], Any]
    min_version: int = WIRE_VERSION  # oldest wire version that may carry it
    encode: bool = True              # False = legacy: decoded forever, never
                                     # emitted (a newer record supersedes it)
    # streaming writer: size pre-pass + in-place emit (see module docstring).
    # None → fallback: the body is built once via ``pack`` and copied in.
    prepare: Callable[[Any], _Prepared] | None = None

    def prepared(self, leaf) -> _Prepared:
        if self.prepare is not None:
            return self.prepare(leaf)
        body = self.pack(leaf)   # legacy fallback: one build, one copy-in
        return (len(body), body)


_RECORDS: dict[int, WireRecord] = {}


def register_record(record: WireRecord) -> WireRecord:
    """Register a record kind (new codecs plug in here; see compression.py)."""
    if not 0 <= record.kind <= 0xFF:
        raise ValueError(f"record kind {record.kind} does not fit the u8 field")
    if record.kind in _RECORDS:
        raise ValueError(
            f"record kind {record.kind} already registered "
            f"as {_RECORDS[record.kind].name!r}"
        )
    _RECORDS[record.kind] = record
    return record


register_record(WireRecord(KIND_RAW, "RAW", None, _raw_body, _decode_array,
                           min_version=1, prepare=_raw_prepare))
register_record(WireRecord(KIND_TERNARY, "TERNARY", TernaryTensor,
                           _ternary_body, _decode_ternary_body, min_version=1,
                           prepare=_ternary_prepare))
register_record(WireRecord(KIND_DOWNCAST, "DOWNCAST", DowncastTensor,
                           _downcast_body, _decode_downcast_body,
                           min_version=2, prepare=_downcast_prepare))
# raw-u32-index top-k is legacy: stored v2 captures decode forever, but
# encoders emit the delta-varint record below instead.
register_record(WireRecord(KIND_TOPK, "TOPK", TopKTensor,
                           _topk_body, _decode_topk_body,
                           min_version=2, encode=False))
register_record(WireRecord(KIND_TOPK_DELTA, "TOPK_DELTA", TopKTensor,
                           _topk_delta_body, _decode_topk_delta_body,
                           min_version=3, prepare=_topk_delta_prepare))


def _leaf_types() -> tuple[type, ...]:
    # union of the record registry's leaf classes and the codec registry's
    # (so a codec registered without a wire record is SEEN as a leaf here
    # and _record_for_leaf can refuse it loudly instead of tree-flattening
    # through it and silently serializing its children as containers).
    own = {r.leaf_type for r in _RECORDS.values() if r.leaf_type is not None}
    return tuple(own | set(wire_leaf_types()))


def _record_for_leaf(leaf, codec_leaf_types: tuple[type, ...] | None = None) -> WireRecord:
    for rec in _RECORDS.values():
        if rec.encode and rec.leaf_type is not None and isinstance(leaf, rec.leaf_type):
            return rec
    if codec_leaf_types is None:
        codec_leaf_types = tuple(wire_leaf_types())
    if isinstance(leaf, codec_leaf_types):
        raise WireError(
            f"wire leaf {type(leaf).__name__} has a registered codec but no "
            f"record kind — call comm.wire.register_record for it"
        )
    return _RECORDS[KIND_RAW]


# --------------------------------------------------------------------------
# Single-tensor codec (used by TernaryTensor.to_bytes / from_bytes).
# --------------------------------------------------------------------------


def encode_tensor(t: TernaryTensor) -> bytes:
    """Serialize one TernaryTensor (header + single TERNARY record body,
    stamped v1 — the TERNARY body is unchanged since v1)."""
    body = _ternary_body(t)
    v = _RECORDS[KIND_TERNARY].min_version
    return _HEADER.pack(WIRE_MAGIC, v, 0, 1, zlib.crc32(body), len(body)) + body


def decode_tensor(data: bytes) -> TernaryTensor:
    body, _, _ = _check_header(data, expect_records=1)
    r = _Reader(body)
    t = _decode_ternary_body(r)
    if r.pos != len(body):
        raise WireError(f"{len(body) - r.pos} trailing bytes after tensor record")
    return t


# --------------------------------------------------------------------------
# Pytree path encoding (dicts + sequences).
# --------------------------------------------------------------------------


def _path_entries(path) -> list[str]:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            if isinstance(p.key, str):
                out.append(f"d:{p.key}")
            elif isinstance(p.key, (int, np.integer)):
                out.append(f"k:{int(p.key)}")   # int dict key ≠ sequence index
            else:
                raise WireError(f"unsupported dict key type {type(p.key).__name__}")
        elif isinstance(p, jax.tree_util.SequenceKey):
            out.append(f"i:{p.idx}")
        elif isinstance(p, jax.tree_util.GetAttrKey):
            out.append(f"d:{p.name}")
        else:  # pragma: no cover - exotic custom nodes
            raise WireError(f"unsupported pytree path entry {p!r}")
    return out


def _parse_entry(e: str) -> tuple[str, Any]:
    if e.startswith("d:"):
        return ("d", e[2:])
    if e.startswith("k:"):
        return ("k", _parse_int(e[2:]))
    if e.startswith("i:"):
        return ("i", _parse_int(e[2:]))
    raise WireError(f"bad path entry {e!r}")


def _parse_int(s: str) -> int:
    try:
        return int(s)
    except ValueError as e:
        raise WireError(f"bad integer path entry {s!r}") from e


def _insert(root: dict, entries: list[str], leaf) -> None:
    node = root
    for i, e in enumerate(entries):
        key = _parse_entry(e)
        if i == len(entries) - 1:
            if key in node and isinstance(node[key], dict):
                raise WireError(f"path collision at {e!r}: leaf under container")
            node[key] = leaf
        else:
            nxt = node.setdefault(key, {})
            if not isinstance(nxt, dict):
                raise WireError(f"path collision at {e!r}: container under leaf")
            node = nxt


def _containerize(node):
    """Rebuild containers from typed keys: ('i', n) nodes → lists,
    ('d', s)/('k', n) nodes → dicts (string / int keys)."""
    if not isinstance(node, dict):
        return node
    tags = {t for t, _ in node}
    if "i" in tags:
        if tags != {"i"}:
            raise WireError("mixed sequence and dict entries at one node")
        idxs = sorted(k for _, k in node)
        if idxs != list(range(len(idxs))):
            raise WireError(f"non-contiguous sequence indices {idxs}")
        return [_containerize(node[("i", i)]) for i in idxs]
    return {k: _containerize(v) for (_, k), v in node.items()}


# --------------------------------------------------------------------------
# Update codec.
# --------------------------------------------------------------------------


def encode_update(tree: Pytree) -> bytes:
    """Serialize an update pytree into one framed, CRC-protected buffer.

    STREAMING: pass 1 prepares every record (exact body size + writer), then
    ONE buffer of the final length is allocated and each record writes its
    framing and array payloads straight into it — no per-record ``bytes``
    concatenation (output is byte-identical to the old join-based builder).

    The header is stamped with the LOWEST wire version able to carry the
    payload's record kinds (v1 for RAW/TERNARY-only traffic — byte-identical
    to what a v1 encoder produced, so old decoders stay compatible; v2 once
    a downcast/top-k record appears)."""
    with obs.span("repro.wire.encode"):
        lt = _leaf_types()  # hoisted: rebuilt per call, not per pytree node
        leaves = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, lt)
        )[0]
        version = min(SUPPORTED_VERSIONS)
        codec_lt = tuple(wire_leaf_types())
        prepared: list = []  # (record prefix: len+path+kind, body bytes | writer)
        total = _HEADER.size
        for path, leaf in leaves:
            p = _PATH_SEP.join(_path_entries(path)).encode("utf-8")
            rec = _record_for_leaf(leaf, codec_lt)
            version = max(version, rec.min_version)
            size, emit = rec.prepared(leaf)
            pfx = struct.pack("<H", len(p)) + p + struct.pack("<B", rec.kind)
            total += len(pfx) + size
            prepared.append((pfx, emit))
        buf = bytearray(total)
        view = memoryview(buf)
        off = _HEADER.size
        for pfx, emit in prepared:
            end = off + len(pfx)
            view[off:end] = pfx
            off = end
            if type(emit) is bytes:       # small record: body is the bytes
                end = off + len(emit)
                view[off:end] = emit
                off = end
            else:                         # large record: memcpy writer
                off = emit(view, off)
        if off != total:  # pragma: no cover - writer/size contract violation
            raise WireError(
                f"record writer emitted {off - _HEADER.size} bytes, "
                f"sized {total - _HEADER.size}"
            )
        _HEADER.pack_into(
            buf, 0, WIRE_MAGIC, version, 0, len(prepared),
            zlib.crc32(view[_HEADER.size:]), total - _HEADER.size,
        )
        return bytes(buf)


def _check_header(
    data: bytes, expect_records: int | None = None
) -> tuple[memoryview, int, int]:
    """Validate framing and integrity; returns (read-only view of the record
    section, n_records, buffer wire version).

    The view aliases ``data`` when it is immutable ``bytes``. Any other
    buffer (a ``bytearray``, a writable ``memoryview``) is copied once into
    ``bytes`` first, so the bytes the CRC covers are the bytes parsed, and a
    caller that refills its buffer cannot change a leaf decoded from it."""
    if not isinstance(data, bytes):
        data = bytes(memoryview(data))
        obs.count("wire.copied_bytes", len(data))
    if len(data) < _HEADER.size:
        raise WireError(f"buffer too short for header: {len(data)} B")
    magic, version, _flags, n_records, crc, body_len = _HEADER.unpack_from(data)
    if magic != WIRE_MAGIC:
        raise WireError(f"bad magic {magic!r} (expected {WIRE_MAGIC!r})")
    if version not in SUPPORTED_VERSIONS:
        raise WireError(
            f"wire version {version} not supported (have {SUPPORTED_VERSIONS})"
        )
    body = memoryview(data)[_HEADER.size:]
    if len(body) != body_len:
        raise WireError(f"body length {len(body)} != header body_len {body_len}")
    if zlib.crc32(body) != crc:
        raise WireError("CRC32 mismatch: payload corrupted in transit")
    if expect_records is not None and n_records != expect_records:
        raise WireError(f"expected {expect_records} records, header says {n_records}")
    return body, n_records, version


def decode_update(data: bytes) -> Pytree:
    """Inverse of ``encode_update``: rebuild the pytree bit-exactly.

    Dict containers round-trip as dicts (string and int keys preserved);
    list/tuple containers come back as lists (index paths carry no
    tuple-vs-list distinction), and attr-style custom nodes (GetAttrKey
    paths) come back as plain dicts keyed by attribute name — leaves are
    always bit-exact, containers normalize to dict/list. A single-leaf
    tree with an empty path decodes to the bare leaf.
    """
    try:
        return _decode_update(data)
    except WireError:
        raise
    except (struct.error, ValueError, TypeError, OverflowError,
            UnicodeDecodeError) as e:
        # any parse failure surfaces as WireError — never a stray exception
        raise WireError(f"malformed wire buffer: {e}") from e


def _decode_records(data: bytes, *, zero_copy: bool = False) -> list[tuple[str, Any]]:
    with obs.span("repro.wire.decode"):
        body, n_records, version = _check_header(data)
        r = _Reader(body, zero_copy=zero_copy)
        out: list[tuple[str, Any]] = []
        for _ in range(n_records):
            path = r.text(r.u16(), "utf-8")
            kind = r.u8()
            rec = _RECORDS.get(kind)
            if rec is None:
                raise WireError(f"unknown record kind {kind}")
            if version < rec.min_version:
                raise WireError(
                    f"record kind {rec.name} requires wire v{rec.min_version}, "
                    f"buffer is v{version}"
                )
            out.append((path, rec.unpack(r)))
        if r.pos != len(body):
            raise WireError(f"{len(body) - r.pos} trailing bytes after last record")
        return out


def decode_update_leaves(
    data: bytes, *, zero_copy: bool = False
) -> list[tuple[str, Any]]:
    """Batched record decode: the flat (path, leaf) list in record order,
    WITHOUT rebuilding containers — the streaming aggregator consumes records
    straight off the buffer. With ``zero_copy=True``, array payloads are
    read-only numpy views into the caller's blob (no copy, no device
    transfer): they alias ``data`` itself when it is immutable ``bytes``,
    and a mutable buffer is copied once first, so refilling it later cannot
    change them. Each view keeps the whole blob alive. With
    ``zero_copy=False`` every array is a jax array of its own.
    ``tree_from_records`` rebuilds the pytree when one is needed."""
    try:
        return _decode_records(data, zero_copy=zero_copy)
    except WireError:
        raise
    except (struct.error, ValueError, TypeError, OverflowError,
            UnicodeDecodeError) as e:
        raise WireError(f"malformed wire buffer: {e}") from e


def tree_leaf_paths(tree: Pytree) -> list[tuple[str, Any]]:
    """Flatten a pytree to (wire path, leaf) pairs — the exact path strings
    ``encode_update`` stamps on records, so a decoded update's record paths
    can be structure-checked against a reference tree without re-encoding
    it (the defense gate's treedef match)."""
    lt = _leaf_types()
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, lt)
    )[0]
    return [(_PATH_SEP.join(_path_entries(p)), leaf) for p, leaf in leaves]


def tree_from_records(pairs: list[tuple[str, Any]]) -> Pytree:
    """Rebuild the pytree from (path, leaf) record pairs (the inverse of the
    flatten ``encode_update`` performed; same container normalization as
    ``decode_update``)."""
    root: dict = {}
    bare_leaf = None
    for path, leaf in pairs:
        if not path:
            if len(pairs) != 1:
                raise WireError("empty path in multi-record update")
            bare_leaf = leaf
        else:
            _insert(root, path.split(_PATH_SEP), leaf)
    if bare_leaf is not None:
        return bare_leaf
    return _containerize(root)


def _decode_update(data: bytes) -> Pytree:
    return tree_from_records(_decode_records(data))


# --------------------------------------------------------------------------
# Incremental / chunked reading (the transport boundary).
# --------------------------------------------------------------------------


# A wire buffer larger than this is a corrupted or hostile length field, not
# a model update — even a full-size fp32 LLM checkpoint stays far below it.
MAX_BODY_BYTES = 1 << 34  # 16 GiB


class StreamDecoder:
    """Incremental wire-buffer framing over an arbitrary chunk stream.

    ``decode_update`` assumes it holds one COMPLETE buffer; a socket hands
    you partial reads. ``feed(chunk)`` accumulates bytes and returns every
    complete wire buffer the stream has finished so far (possibly several
    per chunk, possibly none) — each returned ``bytes`` object is exactly
    one ``encode_update`` output, ready for ``decode_update`` /
    ``decode_update_leaves`` (which re-verify the CRC; this class only
    frames and fail-fasts on the header).

    Failure discipline: a bad magic, unsupported version, or oversized
    ``body_len`` raises ``WireError`` as soon as the 24 header bytes are
    in — the reader never waits for a body it already knows is garbage,
    so a corrupted length field cannot make the caller hang on a recv
    that will never complete. ``close()`` (call at EOF/disconnect) raises
    ``WireError`` if bytes of an unfinished buffer are pending — a torn
    stream surfaces as an error, never as a silent short read.
    """

    def __init__(self, *, max_body_bytes: int = MAX_BODY_BYTES):
        self._buf = bytearray()
        self._need: int | None = None   # total frame length once header known
        self._max_body = int(max_body_bytes)
        self.frames_out = 0
        self.bytes_in = 0

    def _header_check(self) -> int:
        """Validate the buffered header; returns the full frame length."""
        magic, version, _flags, _n, _crc, body_len = _HEADER.unpack_from(
            self._buf
        )
        if magic != WIRE_MAGIC:
            raise WireError(f"bad magic {magic!r} in stream (expected {WIRE_MAGIC!r})")
        if version not in SUPPORTED_VERSIONS:
            raise WireError(
                f"wire version {version} not supported (have {SUPPORTED_VERSIONS})"
            )
        if body_len > self._max_body:
            raise WireError(
                f"body_len {body_len} exceeds stream cap {self._max_body} — "
                "corrupted length field"
            )
        return _HEADER.size + body_len

    def feed(self, chunk: bytes) -> list[bytes]:
        """Absorb one chunk (any size, including empty); return the wire
        buffers completed by it, in stream order."""
        self._buf += chunk
        self.bytes_in += len(chunk)
        out: list[bytes] = []
        while True:
            if self._need is None:
                if len(self._buf) < _HEADER.size:
                    break
                self._need = self._header_check()
            if len(self._buf) < self._need:
                break
            out.append(bytes(self._buf[: self._need]))
            del self._buf[: self._need]
            self._need = None
        self.frames_out += len(out)
        return out

    @property
    def pending_bytes(self) -> int:
        """Bytes buffered toward an incomplete wire buffer."""
        return len(self._buf)

    def close(self) -> None:
        """Declare EOF: a partially-received buffer is a truncation error."""
        if self._buf:
            need = "?" if self._need is None else str(self._need)
            raise WireError(
                f"stream ended mid-buffer: {len(self._buf)} bytes pending "
                f"of {need}"
            )


def decode_update_chunks(chunks) -> Pytree:
    """Decode ONE update delivered as an iterable of byte chunks (the
    chunked-reader convenience over ``StreamDecoder``): raises ``WireError``
    on truncation, trailing garbage, or more than one buffer in the
    stream — never hangs, never returns a short read."""
    dec = StreamDecoder()
    frames: list[bytes] = []
    for chunk in chunks:
        frames.extend(dec.feed(chunk))
        if len(frames) > 1:
            raise WireError("multiple wire buffers in a single-update stream")
    dec.close()
    if len(frames) != 1:
        raise WireError("stream ended before a complete wire buffer arrived")
    return decode_update(frames[0])


def update_nbytes(tree: Pytree) -> int:
    """Measured wire size of a pytree: ``len(encode_update(tree))``."""
    return len(encode_update(tree))
