"""Batched serving front end over the packed-ternary artifact, under load.

``launch.serve`` answers exactly one probe; this module is the long-lived
front end the edge actually runs:

  - **Request batching**: a closed loop coalesces every request that has
    arrived by the time the previous forward finished — up to
    ``max_batch`` — into ONE forward pass, so every weight matmul in the
    batch shares a single pass through the 2-bit ``ternary_matmul``
    kernel. Per-launch overhead (and, on real hardware, the packed-weight
    HBM read) amortizes across the batch; ``benchmarks/bench_serve.py``
    measures the resulting p50/p99-vs-QPS surface.

  - **LRU dequant-cache**: the artifact keeps its NON-matmul wire leaves
    (fp16-downcast embeddings/norms/biases, non-matmul ternary) in wire
    form and materializes them dense on demand through ``LRUDequantCache``
    — a byte-bounded cache, so serving memory is
    packed-weights + cache-capacity instead of the full dense model. Hot
    leaves (touched every forward) stay resident; a tight budget degrades
    to decode-per-forward instead of OOM. Hit/miss/eviction counts are
    exported to the bench record.

The matmul weights themselves are ``PackedTernary`` (2-bit kernel layout,
never dequantized) exactly as in ``launch.serve --packed``.

Demo::

    PYTHONPATH=src python -m repro.launch.serve_loop \
        --requests 64 --qps 200 --max-batch 8
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.wire import decode_update, encode_update
from repro.core import FTTQConfig
from repro.core.compression import (
    CodecSpec,
    compress_pytree,
    decode_wire_leaf,
    is_wire_leaf,
)
from repro.core.ternary import TernaryTensor
from repro.kernels.repack import PackedTernary, repack_to_kernel_layout
from repro.launch.env import configure_compile_cache

Pytree = Any


# --------------------------------------------------------------------------
# LRU dequant-cache.
# --------------------------------------------------------------------------


class LRUDequantCache:
    """Byte-bounded LRU over dense materializations of wire leaves.

    ``get(key, wire_leaf)`` returns the dense array, decoding on miss and
    evicting least-recently-used entries until the live bytes fit
    ``capacity_bytes``. A leaf larger than the whole capacity is decoded,
    returned, and immediately dropped (counted as an eviction) — the cache
    degrades to decode-per-use, it never refuses to serve.
    ``capacity_bytes=0`` disables retention entirely (every get is a miss).
    """

    def __init__(self, capacity_bytes: int):
        if capacity_bytes < 0:
            raise ValueError(f"capacity_bytes must be ≥ 0, got {capacity_bytes}")
        self.capacity_bytes = int(capacity_bytes)
        self._entries: OrderedDict[str, tuple[Any, int]] = OrderedDict()
        self.live_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: str, wire_leaf) -> Any:
        hit = self._entries.get(key)
        if hit is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            return hit[0]
        self.misses += 1
        dense = decode_wire_leaf(wire_leaf)
        nbytes = int(np.asarray(dense).nbytes)
        self._entries[key] = (dense, nbytes)
        self.live_bytes += nbytes
        while self.live_bytes > self.capacity_bytes and self._entries:
            _k, (_v, nb) = self._entries.popitem(last=False)
            self.live_bytes -= nb
            self.evictions += 1
        return dense

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {
            "capacity_bytes": self.capacity_bytes,
            "live_bytes": self.live_bytes,
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": (self.hits / total) if total else 0.0,
        }


# --------------------------------------------------------------------------
# The serving engine.
# --------------------------------------------------------------------------


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


class ServeEngine:
    """Long-lived packed-ternary inference engine with lazy wire leaves.

    The deploy artifact round-trips the real wire codec (compress →
    serialize → decode, CRC verified); 2-D/3-D ternary records repack into
    the 2-bit kernel layout, every OTHER wire leaf stays in wire form and
    is materialized through the LRU dequant-cache at forward time.
    """

    def __init__(self, model_cfg, params: Pytree, *,
                 fttq: FTTQConfig | None = None, residual: str = "fp16",
                 max_batch: int = 8, cache_capacity_bytes: int = 1 << 24):
        if max_batch < 1:
            raise ValueError(f"max_batch must be ≥ 1, got {max_batch}")
        self.model_cfg = model_cfg
        self.max_batch = int(max_batch)
        self.cache = LRUDequantCache(cache_capacity_bytes)
        fttq = fttq if fttq is not None else FTTQConfig()

        wire_tree, _ = compress_pytree(
            params, CodecSpec(kind="ternary", residual=residual, fttq=fttq)
        )
        blob = encode_update(wire_tree)
        self.wire_bytes = len(blob)
        decoded = decode_update(blob)

        # split: matmul ternary → PackedTernary (2-bit, resident); every
        # other wire leaf stays lazy behind the dequant-cache.
        self.packed_weight_bytes = 0
        self.lazy_wire_bytes_dense = 0   # dense size the cache may hold
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(
            decoded, is_leaf=is_wire_leaf
        )
        self._template: list = []        # PackedTernary | _Lazy | dense array
        self._lazy_keys: list[str] = []
        for path, leaf in flat:
            if isinstance(leaf, TernaryTensor) and len(leaf.shape) in (2, 3):
                p = repack_to_kernel_layout(leaf)
                self.packed_weight_bytes += (
                    int(p.packed.size) + int(np.asarray(p.w_q).nbytes)
                )
                self._template.append(p)
            elif is_wire_leaf(leaf):
                key = _path_str(path)
                self._lazy_keys.append(key)
                self.lazy_wire_bytes_dense += int(
                    np.asarray(decode_wire_leaf(leaf)).nbytes
                )
                self._template.append(_Lazy(key, leaf))
            else:
                self._template.append(leaf)
        self.forwards = 0
        self.requests_served = 0

    # -- params resolution -------------------------------------------------

    def resolve_params(self) -> Pytree:
        """The servable tree for ONE forward: lazy wire leaves go through
        the LRU cache (hot layers stay resident), the rest pass through."""
        leaves = [
            self.cache.get(x.key, x.wire) if isinstance(x, _Lazy) else x
            for x in self._template
        ]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- inference ---------------------------------------------------------

    def forward(self, tokens: jax.Array) -> jax.Array:
        """One batched forward through the packed kernels; returns logits."""
        from repro.models.transformer import forward as model_forward

        b = int(tokens.shape[0])
        if b > self.max_batch:
            raise ValueError(f"batch {b} exceeds max_batch {self.max_batch}")
        params = self.resolve_params()
        logits, _cache, _aux = model_forward(self.model_cfg, params, tokens)
        jax.block_until_ready(logits)
        self.forwards += 1
        self.requests_served += b
        return logits

    def stats(self) -> dict:
        return {
            "wire_bytes": self.wire_bytes,
            "packed_weight_bytes": self.packed_weight_bytes,
            "lazy_wire_bytes_dense": self.lazy_wire_bytes_dense,
            "max_batch": self.max_batch,
            "forwards": self.forwards,
            "requests_served": self.requests_served,
            "cache": self.cache.stats(),
        }


@dataclasses.dataclass
class _Lazy:
    """A wire leaf the engine materializes through the dequant-cache."""

    key: str
    wire: Any


# --------------------------------------------------------------------------
# Closed-loop load generation.
# --------------------------------------------------------------------------


@dataclasses.dataclass
class LoadReport:
    """One (offered QPS, max_batch) point of the latency surface."""

    offered_qps: float
    achieved_qps: float
    n_requests: int
    max_batch: int
    p50_ms: float
    p99_ms: float
    mean_ms: float
    mean_batch: float
    wall_s: float               # busy wall-clock of the serving loop
    cache: dict

    def row(self) -> dict:
        return dataclasses.asdict(self)


def run_closed_loop(engine: ServeEngine, *, n_requests: int,
                    offered_qps: float, prompt_len: int = 8,
                    seed: int = 0) -> LoadReport:
    """Drive the engine with a Poisson open-arrival schedule, coalescing
    everything that arrived while the previous forward ran (up to
    ``max_batch``) into the next one.

    The arrival clock is VIRTUAL (deterministic schedule from ``seed``);
    service times are REAL measured forward wall times, so latency =
    completion − arrival mixes a reproducible load pattern with honest
    compute costs. Under-offered load → batches of 1 and latency ≈ forward
    time; past saturation → batches grow toward ``max_batch`` and the
    p99 reflects queueing.
    """
    if n_requests < 1 or offered_qps <= 0:
        raise ValueError("need n_requests ≥ 1 and offered_qps > 0")
    rng = np.random.default_rng(seed)
    inter = rng.exponential(1.0 / offered_qps, size=n_requests)
    arrivals = np.cumsum(inter)
    vocab = int(engine.model_cfg.vocab_size)
    prompts = rng.integers(0, vocab, size=(n_requests, prompt_len))

    # one warmup forward per batch size is NOT taken: the loop itself pays
    # first-touch costs exactly like a cold server would; run a single
    # warmup at batch 1 so jit/interpret setup doesn't distort every point.
    engine.forward(jnp.asarray(prompts[:1]))

    now = 0.0
    busy_s = 0.0
    done = 0
    latencies = np.empty(n_requests)
    batch_sizes = []
    while done < n_requests:
        if arrivals[done] > now:
            now = float(arrivals[done])      # idle until the next arrival
        take = done + 1
        while (take < n_requests and take - done < engine.max_batch
               and arrivals[take] <= now):
            take += 1
        batch = jnp.asarray(prompts[done:take])
        t0 = time.perf_counter()
        engine.forward(batch)
        dt = time.perf_counter() - t0
        busy_s += dt
        now += dt
        latencies[done:take] = now - arrivals[done:take]
        batch_sizes.append(take - done)
        done = take

    lat_ms = latencies * 1e3
    return LoadReport(
        offered_qps=float(offered_qps),
        achieved_qps=float(n_requests / now),
        n_requests=int(n_requests),
        max_batch=engine.max_batch,
        p50_ms=float(np.percentile(lat_ms, 50)),
        p99_ms=float(np.percentile(lat_ms, 99)),
        mean_ms=float(lat_ms.mean()),
        mean_batch=float(np.mean(batch_sizes)),
        wall_s=float(busy_s),
        cache=engine.cache.stats(),
    )


def demo_model(d_model: int = 32, n_layers: int = 2, vocab: int = 64):
    """The tiny dense LM the CLI demo and the bench serve."""
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="serve-demo", family="dense", n_layers=n_layers,
                      d_model=d_model, vocab_size=vocab, n_heads=4,
                      n_kv_heads=2, d_ff=2 * d_model)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Closed-loop load against the packed-ternary serve engine"
    )
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--cache-bytes", type=int, default=1 << 24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg, params = demo_model(args.d_model, args.layers)
    engine = ServeEngine(cfg, params, max_batch=args.max_batch,
                         cache_capacity_bytes=args.cache_bytes)
    report = run_closed_loop(engine, n_requests=args.requests,
                             offered_qps=args.qps,
                             prompt_len=args.prompt_len, seed=args.seed)
    print(json.dumps({"engine": engine.stats(), "load": report.row()},
                     indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
