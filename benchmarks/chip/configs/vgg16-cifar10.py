"""VGG16 for CIFAR-10 (arXiv:2309.10367 Table 1): the benchmark's own
weights, data, plain reference and FLOP count, from the sizes in
``vgg16-cifar10.json``.

Parameters keep the program's layout (one top-level key per freeze
unit: ``conv0`` .. ``conv12``, ``dense0``), which is the layout the
published model has: a 3x3 'same' convolution with bias, batch
normalisation on batch statistics (scale, shift, and moving statistics
that are counted but not read), ReLU, a 2x2 max-pool after each stage,
global average pooling (1x1 after five pools, so the same as Keras'
Flatten) and one dense layer.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def units(cfg) -> List[str]:
    n = sum(k for k, _ in cfg["stages"])
    return [f"conv{i}" for i in range(n)] + ["dense0"]


def init_params(cfg, key):
    """Weights in the program's layout, float32 (call under ``jit``)."""
    ks = iter(jax.random.split(key, len(units(cfg))))
    k = cfg["conv_kernel"]
    params, cin, i = {}, cfg["image"][2], 0
    for n, cout in cfg["stages"]:
        for _ in range(n):
            params[f"conv{i}"] = {
                "w": jax.random.normal(next(ks), (k, k, cin, cout))
                / math.sqrt(k * k * cin),
                "b": jnp.zeros((cout,)),
                "bn_g": jnp.ones((cout,)), "bn_b": jnp.zeros((cout,)),
                "bn_mu": jnp.zeros((cout,)), "bn_var": jnp.ones((cout,)),
            }
            cin, i = cout, i + 1
    params["dense0"] = {
        "w": jax.random.normal(next(ks), (cin, cfg["classes"]))
        / math.sqrt(cin),
        "b": jnp.zeros((cfg["classes"],)),
    }
    return params


def make_data(cfg, n_clients: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """Synthetic CIFAR-10-shaped images: smooth class prototypes plus
    noise, ``examples_per_client`` per client."""
    rng = np.random.default_rng(seed)
    h, w, c = cfg["image"]
    protos = rng.standard_normal((cfg["classes"], h, w, c), np.float32)
    for _ in range(2):
        protos = (protos + np.roll(protos, 1, 1) + np.roll(protos, 1, 2)) / 3
    per = cfg["examples_per_client"]
    out = []
    for _ in range(n_clients):
        y = rng.integers(0, cfg["classes"], per).astype(np.int32)
        x = protos[y] + 0.35 * rng.standard_normal((per, h, w, c),
                                                    np.float32)
        out.append({"x": x, "y": y})
    return out


def forward(cfg, params, x):
    """Plain forward pass: images (B, H, W, C) -> logits (B, classes)."""
    i = 0
    for n, _ in cfg["stages"]:
        for _ in range(n):
            p = params[f"conv{i}"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
            mu = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2))
            x = (x - mu) / jnp.sqrt(var + cfg["bn_eps"]) * p["bn_g"] \
                + p["bn_b"]
            x = jnp.maximum(x, 0)
            i += 1
        b, h, w, c = x.shape
        x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["dense0"]["w"] + params["dense0"]["b"]


def loss(cfg):
    """``(params, batch) -> mean softmax cross-entropy``."""
    def fn(params, batch):
        logits = forward(cfg, params, batch["x"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)
    return fn


def layer_macs(cfg) -> List[int]:
    """Multiply-accumulates per example of each unit's matmul or
    convolution, in unit order (batch norm, ReLU and pooling are not
    counted)."""
    h, w, cin = cfg["image"]
    k = cfg["conv_kernel"]
    out = []
    for n, cout in cfg["stages"]:
        for _ in range(n):
            out.append(h * w * k * k * cin * cout)
            cin = cout
        h, w = h // 2, w // 2
    out.append(cin * cfg["classes"])
    return out

