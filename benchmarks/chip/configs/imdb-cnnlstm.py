"""IMDB sentiment CNN-LSTM (arXiv:2309.10367 Table 2): the benchmark's
own weights, data, plain reference and FLOP count, from the sizes in
``imdb-cnnlstm.json``.

Parameters keep the program's layout, one top-level key per freeze
unit: ``embed_small`` (the embedding table), ``conv0``, ``lstm0``
(input and recurrent kernels of the four gates i, f, g, o side by side,
one bias) and ``dense0``.  The forward pass: embedding lookup, 1-D
convolution with ReLU, max-pool over windows of ``pool`` positions, an
LSTM whose last hidden state feeds the dense layer.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

UNITS = ["embed_small", "conv0", "lstm0", "dense0"]


def units(cfg) -> List[str]:
    return list(UNITS)


def init_params(cfg, key):
    """Weights in the program's layout, float32 (call under ``jit``)."""
    ks = jax.random.split(key, 5)
    e, f, h = cfg["embed"], cfg["conv_filters"], cfg["lstm"]
    k = cfg["conv_kernel"]
    return {
        "embed_small": {"table": 0.05 * jax.random.normal(
            ks[0], (cfg["vocab"], e))},
        "conv0": {"w": jax.random.normal(ks[1], (k, e, f))
                  / math.sqrt(k * e), "b": jnp.zeros((f,))},
        "lstm0": {"wx": jax.random.normal(ks[2], (f, 4 * h)) / math.sqrt(f),
                  "wh": jax.random.normal(ks[3], (h, 4 * h)) / math.sqrt(h),
                  "b": jnp.zeros((4 * h,))},
        "dense0": {"w": jax.random.normal(ks[4], (h, cfg["classes"]))
                   / math.sqrt(h), "b": jnp.zeros((cfg["classes"],))},
    }


def make_data(cfg, n_clients: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """Synthetic reviews: a Zipf background with label-indicative tokens
    at 15 % of positions, ``examples_per_client`` per client."""
    rng = np.random.default_rng(seed)
    n = n_clients * cfg["examples_per_client"]
    v, s, sig = cfg["vocab"], cfg["seq_len"], 200
    y = rng.integers(0, cfg["classes"], n)
    base = rng.zipf(1.3, (n, s)).clip(1, v - 1)
    tok = 100 + sig * y[:, None] + rng.integers(0, sig, (n, s))
    x = np.where(rng.random((n, s)) < 0.15, tok, base).astype(np.int32)
    per = cfg["examples_per_client"]
    return [{"x": x[i * per:(i + 1) * per], "y": y[i * per:(i + 1) * per]
             .astype(np.int32)} for i in range(n_clients)]


def forward(cfg, params, tokens):
    """Plain forward pass: tokens (B, S) -> logits (B, classes)."""
    x = params["embed_small"]["table"][tokens]
    p = params["conv0"]
    k = cfg["conv_kernel"]
    pad = ((k - 1) // 2, k // 2)                       # 'same'
    x = jax.lax.conv_general_dilated(
        x, p["w"], (1,), [pad], dimension_numbers=("NWC", "WIO", "NWC"))
    x = jnp.maximum(x + p["b"], 0)
    b, s, c = x.shape
    q = cfg["pool"]
    x = x[:, : (s // q) * q].reshape(b, s // q, q, c).max(axis=2)
    p = params["lstm0"]
    h = jnp.zeros((b, cfg["lstm"]), x.dtype)
    cell = jnp.zeros((b, cfg["lstm"]), x.dtype)
    for t in range(x.shape[1]):
        z = x[:, t] @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        cell = jax.nn.sigmoid(f) * cell + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(cell)
    return h @ params["dense0"]["w"] + params["dense0"]["b"]


def loss(cfg):
    """``(params, batch) -> mean softmax cross-entropy``."""
    def fn(params, batch):
        logits = forward(cfg, params, batch["x"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)
    return fn


def layer_macs(cfg) -> List[int]:
    """Multiply-accumulates per example of each unit, in unit order.
    The embedding is a lookup: no multiply-accumulates."""
    s, e, f, h = cfg["seq_len"], cfg["embed"], cfg["conv_filters"], cfg["lstm"]
    steps = s // cfg["pool"]
    return [0,
            s * cfg["conv_kernel"] * e * f,
            steps * (f + h) * 4 * h,
            h * cfg["classes"]]

