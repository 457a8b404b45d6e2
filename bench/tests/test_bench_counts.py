"""Work counts from shapes, and the peaks table."""

import jax
import numpy as np
import pytest

import counts

OLMO = dict(hidden_size=2048, intermediate_size=8192, num_attention_heads=16,
            num_key_value_heads=16, num_hidden_layers=16, vocab_size=50304,
            tie_word_embeddings=True)


def test_resnet18s_size_and_flops():
    assert counts.resnet18s_params() == 594_378
    assert counts.resnet18s_train_flops() == pytest.approx(1.21e9, rel=0.01)


def test_resnet18s_params_match_the_program():
    from repro.models.paper_models import init_resnet_cifar

    shapes = jax.eval_shape(lambda k: init_resnet_cifar(k), jax.random.PRNGKey(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes)) == 594_378


def test_olmo_1b_sizes():
    assert counts.lm_quantizable_params(OLMO) == 1_073_741_824
    assert counts.lm_embedding_params(OLMO) == 103_022_592


def test_fanin_and_requantize_bytes():
    flops, nbytes = counts.fanin_cost(16, 1000)
    assert nbytes == 16 * 1000 / 4 + 4 * 1000 and flops == 2 * 16 * 1000
    assert counts.requantize_cost(1000)[1] == 4 * 1000 + 1000 / 4


def test_packed_matmul_bytes():
    flops, nbytes = counts.packed_matmul_cost(8, 2048, 8192)
    assert flops == 2 * 8 * 2048 * 8192
    assert nbytes == 2048 * 8192 / 4 + 4 * 8 * 2048 + 4 * 8 * 8192


def test_decode_step_bytes():
    # 268 MB of codes, 412 MB of f32 head, 2·16 layers of K/V per position
    got = counts.lm_decode_step_bytes(OLMO, batch=8, ctx=192)
    assert got == pytest.approx(1_073_741_824 / 4 + 4 * 50304 * 2048 + 16 * 8 * 192 * 2 * 2048 * 4)


def test_peaks_known_and_unknown():
    pk = counts.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    assert counts.roof_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert counts.roof_seconds(0, 819e9, pk) == pytest.approx(1.0)
