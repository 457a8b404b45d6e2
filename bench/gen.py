"""Seeded inputs: model weights, the CIFAR-shaped mixture, its non-IID
partition, client perturbations and silo weights. Everything the program
receives is made here from ``--seed``; the reference regenerates any leaf on
its own from the same (seed, tag, leaf index), so it never reads what the
program was handed or made.

The generators follow the program's own ones (``repro.data.synthetic``,
``repro.data.federated.partition_noniid``, the payload pool of
``repro.fed.fleet``) with two changes: the labels are exactly balanced, so
every seed gives every client the same number of samples, and the weights
and noise are drawn on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A numpy generator from a seed of any size and integer tags (negative
    tags, as warm-up units take, are folded to non-negative words)."""
    return np.random.default_rng([int(t) & (2**63 - 1) for t in (seed, *tags)])


def key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a seed of any size and integer tags."""
    return jax.random.PRNGKey(int(rng(seed, *tags).integers(0, 2**31 - 1)))


def leaf_value(k: jax.Array, i: int, shape, scale: float, dtype=jnp.float32):
    """Leaf ``i`` of a tree drawn from key ``k``: normal × scale, or a
    constant where ``scale`` is given as ("const", value)."""
    if isinstance(scale, tuple):
        return jnp.full(shape, scale[1], dtype)
    return (jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
            * jnp.float32(scale)).astype(dtype)


def tree_paths(shapes) -> list[str]:
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def params(shapes, scales: list, k: jax.Array):
    """The whole tree in one jitted call on the device."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    sh = [tuple(l.shape) for l in leaves]

    @jax.jit
    def make(k):
        return jax.tree_util.tree_unflatten(
            treedef, [leaf_value(k, i, s, c) for i, (s, c) in enumerate(zip(sh, scales))])

    return make(k)


def perturb_leaf(base, k: jax.Array, i: int, sigma: float):
    """One client's leaf: the global leaf plus N(0, sigma²) noise, as the
    program's payload pool draws it (``repro.fed.fleet``), on the device."""
    return base + jnp.float32(sigma) * jax.random.normal(
        jax.random.fold_in(k, i), base.shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("sigma",))
def perturbed(tree, k: jax.Array, sigma: float):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [perturb_leaf(l, k, i, sigma) for i, l in enumerate(leaves)])


def cifar_mixture(seed: int, n: int, classes: int, hw: tuple, noise: float):
    """Balanced mixture of Gaussians in the CIFAR shape: class centers
    N(0, 1), samples center + noise·N(0, 1), exactly n/classes per class."""
    dim = int(np.prod(hw))
    kc, kx, ky = jax.random.split(key(seed, 1), 3)

    @jax.jit
    def make(kc, kx, ky):
        y = jax.random.permutation(ky, jnp.repeat(jnp.arange(classes), n // classes))
        centers = jax.random.normal(kc, (classes, dim))
        x = centers[y] + noise * jax.random.normal(kx, (n, dim))
        return x.reshape((n,) + tuple(hw)), y.astype(jnp.int32)

    x, y = make(kc, kx, ky)
    return np.asarray(x, np.float32), np.asarray(y, np.int32)


def noniid_shards(y: np.ndarray, n_clients: int, per_client: int, seed: int) -> list[np.ndarray]:
    """Index sets of a label partition: client k holds classes
    (k·N_c + j) mod classes, each class split evenly among its holders
    (the construction of ``repro.data.federated.partition_noniid``)."""
    r = rng(seed, 2)
    classes = np.unique(y)
    owners: dict = {int(c): [] for c in classes}
    for k in range(n_clients):
        for j in range(per_client):
            owners[int(classes[(k * per_client + j) % len(classes)])].append(k)
    parts: dict = {k: [] for k in range(n_clients)}
    for c, ks in owners.items():
        idx = np.where(y == c)[0]
        r.shuffle(idx)
        for holder, shard in zip(ks, np.array_split(idx, len(ks))):
            parts[holder].append(shard)
    out = []
    for k in range(n_clients):
        sel = np.concatenate(parts[k])
        r.shuffle(sel)
        out.append(sel)
    return out


def silo_weights(seed: int, n: int, spec: dict) -> np.ndarray:
    """|D_k| of each upload: equal, or lognormal(0, sigma) scaled to a mean."""
    if spec["kind"] == "equal":
        return np.full(n, float(spec["value"]))
    w = rng(seed, 3).lognormal(0.0, spec["sigma"], n)
    return np.round(w / w.mean() * spec["mean"]).clip(1.0)
