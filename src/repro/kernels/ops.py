"""jit'd public wrappers over the Pallas kernels with automatic backend
dispatch: real Pallas lowering on a TPU, interpret=True elsewhere. The
tests run on the CPU with interpret kernels (the kernel body evaluated by
XLA's CPU backend, for correctness only); the compiled kernels run on the
chip, where ``python chip_smoke.py`` drives them and checks that no kernel
was interpreted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import pack2bit as _pack
from repro.kernels import ternary_matmul as _mm
from repro.kernels import ternary_quantize as _tq
from repro.kernels import ref as _ref


def use_interpret(interpret: bool | None = None) -> bool:
    """An explicit ``interpret`` wins; ``None`` interprets unless the work
    lands on a TPU — the default device when one is set (``with
    jax.default_device(...)``), else the default backend."""
    if interpret is not None:
        return interpret
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() != "tpu"
    return (dev if isinstance(dev, str) else dev.platform) != "tpu"


def fttq_apply(theta: jax.Array, t_k: float, *, interpret: bool | None = None):
    """Full FTTQ for one 2-D layer: stats (jnp reductions) + fused Pallas apply.

    Returns (I_t int8, θ_t, w_q) — w_q initialized at the Prop-4.1 optimum.
    """
    interp = use_interpret(interpret)
    absw = jnp.abs(theta)
    mx = jnp.max(absw) + 1e-8
    inv_scale = 1.0 / mx
    delta = t_k * jnp.mean(absw) * inv_scale  # Δ over scaled weights (eq. 8)
    sel = absw * inv_scale > delta
    w_q = jnp.sum(jnp.where(sel, absw * inv_scale, 0.0)) / (jnp.sum(sel) + 1e-8)
    i_t, theta_t = _tq.ternary_quantize(
        theta, inv_scale, delta, w_q, interpret=interp
    )
    return i_t, theta_t, w_q


def pack2bit(i_t: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    interp = use_interpret(interpret)
    return _pack.pack2bit(i_t, interpret=interp)


def unpack2bit(packed: jax.Array, dtype=jnp.int8, *, interpret: bool | None = None):
    interp = use_interpret(interpret)
    return _pack.unpack2bit(packed, dtype=dtype, interpret=interp)


def ternary_matmul(
    x: jax.Array, packed_w: jax.Array, w_q: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    interp = use_interpret(interpret)
    return _mm.ternary_matmul(x, packed_w, w_q, interpret=interp)


# re-export oracles for convenience
ternary_quantize_ref = _ref.ternary_quantize_ref
pack2bit_ref = _ref.pack2bit_ref
unpack2bit_ref = _ref.unpack2bit_ref
ternary_matmul_ref = _ref.ternary_matmul_ref
