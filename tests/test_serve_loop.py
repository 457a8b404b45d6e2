"""Serving under load: the batched packed-ternary engine must produce the
same logits as the one-shot deploy path, keep its dequant-cache within its
byte budget, and the closed loop must report a sane latency surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FTTQConfig
from repro.launch.serve_loop import (
    LRUDequantCache,
    ServeEngine,
    demo_model,
    run_closed_loop,
)


@pytest.fixture(scope="module")
def tiny():
    return demo_model(d_model=32, n_layers=2)


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    return ServeEngine(cfg, params, max_batch=4)


# --------------------------------------------------------------------------
# LRU dequant-cache.
# --------------------------------------------------------------------------


def _wire_leaf(shape, seed=0):
    from repro.core.compression import DowncastTensor

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return x, DowncastTensor(data=x.astype(jnp.float16), orig_dtype="float32")


def test_cache_hit_miss_eviction_accounting():
    dense_a, wire_a = _wire_leaf((8, 8), 1)   # 256 B dense
    dense_b, wire_b = _wire_leaf((8, 8), 2)
    cache = LRUDequantCache(capacity_bytes=300)   # holds exactly one

    out = cache.get("a", wire_a)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(wire_a.restore()))
    assert (cache.hits, cache.misses, cache.evictions) == (0, 1, 0)
    cache.get("a", wire_a)
    assert cache.hits == 1
    cache.get("b", wire_b)                        # evicts a
    assert cache.evictions == 1 and cache.live_bytes <= 300
    cache.get("a", wire_a)                        # miss again: was evicted
    assert cache.misses == 3
    stats = cache.stats()
    assert stats["entries"] == 1 and 0 < stats["hit_rate"] < 1


def test_cache_capacity_zero_never_retains():
    _dense, wire = _wire_leaf((4, 4))
    cache = LRUDequantCache(0)
    for _ in range(3):
        cache.get("k", wire)
    assert cache.hits == 0 and cache.misses == 3
    assert cache.live_bytes == 0 and cache.evictions == 3


def test_cache_oversized_leaf_still_served():
    _dense, wire = _wire_leaf((32, 32))           # 4 KiB dense
    cache = LRUDequantCache(16)
    out = cache.get("big", wire)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(wire.restore()))
    assert cache.live_bytes <= 16 and cache.evictions == 1


def test_cache_rejects_negative_capacity():
    with pytest.raises(ValueError, match="capacity_bytes"):
        LRUDequantCache(-1)


# --------------------------------------------------------------------------
# Engine correctness.
# --------------------------------------------------------------------------


def test_engine_logits_match_one_shot_deploy(tiny, engine):
    """The lazy-wire-leaf engine must serve the SAME function as
    launch.serve's ternary_deploy(packed=True) — same codec spec, same
    wire round-trip, same kernels."""
    from repro.launch.serve import ternary_deploy
    from repro.models.transformer import forward

    cfg, params = tiny
    served, blob, _, _ = ternary_deploy(
        params, FTTQConfig(), packed=True, residual="fp16")
    assert engine.wire_bytes == len(blob)      # identical artifact
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                              cfg.vocab_size)
    le = engine.forward(toks)
    lr, _, _ = forward(cfg, served, toks)
    np.testing.assert_allclose(np.asarray(le), np.asarray(lr),
                               rtol=1e-5, atol=1e-5)


def test_engine_packed_weights_stay_2bit(engine):
    # packed matmul weights occupy far less than their dense fp32 size
    assert 0 < engine.packed_weight_bytes < engine.lazy_wire_bytes_dense
    toks = jnp.zeros((1, 4), jnp.int32)
    engine.forward(toks)
    engine.forward(toks)            # second forward hits the warm cache
    s = engine.stats()
    assert s["cache"]["hits"] > 0


def test_engine_rejects_oversized_batch(engine, tiny):
    cfg, _ = tiny
    toks = jnp.zeros((engine.max_batch + 1, 4), jnp.int32)
    with pytest.raises(ValueError, match="max_batch"):
        engine.forward(toks)
    with pytest.raises(ValueError, match="max_batch"):
        ServeEngine(cfg, tiny[1], max_batch=0)


def test_engine_tight_cache_still_correct(tiny):
    """With a cache too small for even one leaf the engine decodes every
    forward — slower, never wrong, never over budget."""
    from repro.models.transformer import forward

    cfg, params = tiny
    tight = ServeEngine(cfg, params, max_batch=2, cache_capacity_bytes=64)
    roomy = ServeEngine(cfg, params, max_batch=2)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 5), 0,
                              cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(tight.forward(toks)),
                               np.asarray(roomy.forward(toks)),
                               rtol=1e-6, atol=1e-6)
    assert tight.cache.live_bytes <= 64
    assert tight.cache.evictions > 0


# --------------------------------------------------------------------------
# Closed-loop load generation.
# --------------------------------------------------------------------------


def test_closed_loop_report_sanity(engine):
    rep = run_closed_loop(engine, n_requests=6, offered_qps=500.0,
                          prompt_len=4, seed=1)
    assert rep.n_requests == 6
    assert rep.p99_ms >= rep.p50_ms > 0
    assert rep.mean_ms > 0 and rep.wall_s > 0
    assert 1.0 <= rep.mean_batch <= engine.max_batch
    assert rep.achieved_qps > 0
    row = rep.row()
    assert row["offered_qps"] == 500.0 and "cache" in row


def test_closed_loop_batches_under_pressure(tiny):
    """Offered load far past capacity must coalesce requests: the mean
    batch size exceeds 1 and approaches max_batch."""
    cfg, params = tiny
    eng = ServeEngine(cfg, params, max_batch=4)
    rep = run_closed_loop(eng, n_requests=8, offered_qps=10_000.0,
                          prompt_len=4, seed=2)
    assert rep.mean_batch > 1.5


def test_closed_loop_validates_args(engine):
    with pytest.raises(ValueError):
        run_closed_loop(engine, n_requests=0, offered_qps=1.0)
    with pytest.raises(ValueError):
        run_closed_loop(engine, n_requests=1, offered_qps=0.0)
