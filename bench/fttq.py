"""Plain FTTQ quantizers (arXiv:2003.03564 §III.A–B), written out in
``jax.numpy`` for the reference. A "segment" is what one scale covers: the
whole tensor, or each slice along the leading axis of a stacked tensor
(ndim ≥ 3), as the wire carries per-layer scales.

- client upload (Alg. 1, eqs. 6–12): θ_s = θ / (max|θ| + 1e-8),
  Δ = 0.7 · mean|θ_s|, I = sign(θ_s) · [|θ_s| > Δ], and the scale at its
  Prop-4.1 optimum mean(|θ| over I ≠ 0);
- server broadcast (Alg. 2): the same with the fixed Δ = 0.05 and the
  scale mean(|θ_s| over I ≠ 0) · (max|θ| + 1e-8);
- deploy artifact: the client rule over the whole tensor as one segment,
  with the broadcast's form of the scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-8
T_K = 0.7
SERVER_DELTA = 0.05


def _per_segment(fn, x, stacked: bool):
    return jax.vmap(fn)(x) if stacked else fn(x)


def client(theta, stacked: bool):
    """(codes in {-1,0,1} as theta's dtype, scale per segment)."""
    def one(t):
        s = t / (jnp.max(jnp.abs(t)) + EPS)
        d = T_K * jnp.mean(jnp.abs(s))
        sel = jnp.abs(s) > d
        wq = jnp.sum(jnp.where(sel, jnp.abs(t), 0)) / (jnp.sum(sel).astype(t.dtype) + EPS)
        return jnp.sign(s) * sel.astype(t.dtype), wq
    return _per_segment(one, theta, stacked)


def server(a, stacked: bool, delta: float = SERVER_DELTA):
    """(codes, θ_s, scale per segment) of the broadcast re-quantize."""
    def one(t):
        denom = jnp.max(jnp.abs(t)) + EPS
        s = t / denom
        sel = jnp.abs(s) > delta
        n = jnp.sum(sel).astype(jnp.float32)
        scale = jnp.sum(jnp.where(sel, jnp.abs(s), 0).astype(jnp.float32)) / (n + EPS) * denom
        return jnp.sign(s) * sel.astype(t.dtype), s, scale
    return _per_segment(one, a, stacked)


def deploy(theta):
    """(codes, scale) of the deploy artifact: one segment per tensor."""
    denom = jnp.max(jnp.abs(theta)) + EPS
    s = theta / denom
    sel = jnp.abs(s) > T_K * jnp.mean(jnp.abs(s))
    n = jnp.sum(sel).astype(jnp.float32)
    scale = jnp.sum(jnp.where(sel, jnp.abs(s), 0).astype(jnp.float32)) / (n + EPS) * denom
    return jnp.sign(s) * sel.astype(theta.dtype), scale
