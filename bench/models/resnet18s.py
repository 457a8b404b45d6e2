"""ResNet18* of the paper (arXiv:2003.03564 §V.A), as the program runs it
(``repro.models.paper_models``), and its plain reference.

Eight basic blocks of two 3×3 convolutions, every one at 64 channels, a
3×3 stem, stride 2 at blocks 2, 4 and 6 with a max-pooled shortcut,
GroupNorm of 8 groups in place of BatchNorm, global average pooling and a
linear head: 594,378 parameters on 32×32×3 inputs with 10 classes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def program_shapes(cfg: dict):
    from repro.models.paper_models import init_resnet_cifar

    return jax.eval_shape(lambda k: init_resnet_cifar(
        k, n_classes=cfg["classes"], width=cfg["width"]), jax.random.PRNGKey(0))


def program_apply():
    from repro.models.paper_models import resnet_cifar

    return resnet_cifar


def init_scales(cfg: dict, paths: list[str], shapes: list[tuple]) -> list:
    """He-normal convolutions, LeCun-normal head, GroupNorm scale 1, biases 0."""
    out = []
    for p, s in zip(paths, shapes):
        if p.endswith("scale"):
            out.append(("const", 1.0))
        elif p.endswith("bias"):
            out.append(("const", 0.0))
        elif len(s) == 4:
            out.append(float(np.sqrt(2.0 / np.prod(s[:-1]))))
        else:
            out.append(float(1.0 / np.sqrt(s[0])))
    return out


def quantizable(path: str, shape: tuple) -> bool:
    """Convolutions and the head matrix are ternary; norms and biases f32."""
    return len(shape) >= 2 and not path.endswith(("scale", "bias"))


def _conv(x, w, stride, dtype):
    return jax.lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=dtype)


def _gn(x, scale, bias, groups):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups)
    mu = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mu), axis=(1, 2, 4), keepdims=True)
    g = (g - mu) / jnp.sqrt(var + 1e-5)
    return g.reshape(b, h, w, c) * scale + bias


def forward(p, x, *, groups: int = 8, dtype=jnp.float32):
    """Logits (B, classes) of images x (B, 32, 32, 3)."""
    h = jax.nn.relu(_gn(_conv(x, p["stem"]["w"], 1, dtype), p["stem_norm"]["scale"],
                        p["stem_norm"]["bias"], groups))
    for b in range(8):
        q = p[f"block{b}"]
        stride = 2 if b in (2, 4, 6) else 1
        y = jax.nn.relu(_gn(_conv(h, q["conv1"]["w"], stride, dtype),
                            q["norm1"]["scale"], q["norm1"]["bias"], groups))
        y = _gn(_conv(y, q["conv2"]["w"], 1, dtype), q["norm2"]["scale"],
                q["norm2"]["bias"], groups)
        if stride != 1:
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "SAME")
        h = jax.nn.relu(h + y)
    h = jnp.mean(h, axis=(1, 2))
    return jnp.dot(h.astype(dtype), p["head"]["w"].astype(dtype),
                   precision=jax.lax.Precision.HIGHEST) + p["head"]["bias"]
