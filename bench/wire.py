"""A plain reader of the update wire format (docs/WIRE_FORMAT.md §1), kept
apart from the program's codec so that the correctness check reads what the
program sent with code that the program did not write. Only the record
kinds a T-FedAvg broadcast carries are read: RAW (0) and TERNARY (1).
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

HEADER = struct.Struct("<4sHHIIQ")
MAGIC = b"TFW1"


class Reader:
    def __init__(self, buf: bytes, off: int):
        self.buf, self.off = buf, off

    def take(self, n: int) -> bytes:
        out = self.buf[self.off:self.off + n]
        if len(out) != n:
            raise ValueError("wire buffer truncated")
        self.off += n
        return out

    def unpack(self, fmt: str):
        s = struct.Struct("<" + fmt)
        return s.unpack(self.take(s.size))

    def meta(self) -> tuple[np.dtype, tuple]:
        (n,) = self.unpack("B")
        dtype = np.dtype(self.take(n).decode("ascii"))
        (ndim,) = self.unpack("B")
        dims = self.unpack(f"{ndim}I") if ndim else ()
        return dtype, tuple(int(d) for d in dims)


def unpack_codes(packed, shape):
    """2-bit codes, four to a byte, low bits first → values in {-1, 0, 1}
    (int8) of the logical shape; runs on the device."""
    import jax.numpy as jnp

    n = int(np.prod(shape))
    p = jnp.asarray(packed)
    codes = jnp.stack([(p >> s) & 3 for s in (0, 2, 4, 6)], axis=1).reshape(-1)[:n]
    return (codes.astype(jnp.int8) - 1).reshape(shape)


def read_update(buf: bytes) -> dict:
    """path → ("raw", array) or ("ternary", packed uint8 bytes, logical
    shape, scale array). The path joins the pytree keys with '/'."""
    magic, version, flags, n_rec, crc, body_len = HEADER.unpack_from(buf, 0)
    if magic != MAGIC or flags != 0:
        raise ValueError("not an update buffer")
    if len(buf) - HEADER.size != body_len:
        raise ValueError("body length mismatch")
    if zlib.crc32(memoryview(buf)[HEADER.size:]) != crc:
        raise ValueError("CRC mismatch")
    r = Reader(buf, HEADER.size)
    out = {}
    for _ in range(n_rec):
        (plen,) = r.unpack("H")
        path = "/".join(e.split(":", 1)[1] for e in r.take(plen).decode().split("\x1f") if e)
        (kind,) = r.unpack("B")
        if kind == 0:
            dtype, shape = r.meta()
            (nbytes,) = r.unpack("Q")
            out[path] = ("raw", np.frombuffer(r.take(nbytes), dtype).reshape(shape))
        elif kind == 1:
            _, shape = r.meta()
            sdtype, sshape = r.meta()
            scale = np.frombuffer(r.take(int(np.prod(sshape)) * sdtype.itemsize),
                                  sdtype).reshape(sshape)
            (nbytes,) = r.unpack("Q")
            if nbytes != -(-int(np.prod(shape)) // 4):
                raise ValueError(f"{path}: {nbytes} code bytes for shape {shape}")
            out[path] = ("ternary", np.frombuffer(r.take(nbytes), np.uint8), shape, scale)
        else:
            raise ValueError(f"record kind {kind} is not part of a T-FedAvg broadcast")
    if r.off != len(buf):
        raise ValueError("trailing bytes")
    return out
