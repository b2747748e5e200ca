"""Paper-scale local tasks for the FL simulation (the paper's MLP /
MnistNet / CNNCifar / Transformer class of models, sized for CPU with up to
60 vmapped workers).

A Task is a tiny struct of pure functions:
    init(key) -> params
    loss(params, x, y, mask) -> scalar (masked mean)
    accuracy(params, x, y, mask) -> scalar
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Task:
    name: str
    init: Callable
    loss: Callable
    accuracy: Callable


def _masked_ce(logits, y, mask):
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _masked_acc(logits, y, mask):
    correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
    return (correct * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# MLP (paper's MLP on MNIST)
# ---------------------------------------------------------------------------

def mlp_task(input_dim: int, num_classes: int, hidden: int = 64) -> Task:
    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (input_dim, hidden)) * (input_dim ** -0.5),
            "b1": jnp.zeros(hidden),
            "w2": jax.random.normal(k2, (hidden, num_classes)) * (hidden ** -0.5),
            "b2": jnp.zeros(num_classes),
        }

    def apply(p, x):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    return Task("mlp",
                init,
                lambda p, x, y, m: _masked_ce(apply(p, x), y, m),
                lambda p, x, y, m: _masked_acc(apply(p, x), y, m))


# ---------------------------------------------------------------------------
# CNN (paper's MnistNet/CNNCifar class) on [H, W, C] images
# ---------------------------------------------------------------------------

def _pool_corners(x):
    """The four stride-2 corners of every 2×2 window of an even-sized NHWC
    array, in window order (0,0), (0,1), (1,0), (1,1)."""
    n, h, w, c = x.shape
    return [jax.lax.slice(x, (0, r, s, 0), (n, h, w, c), (1, 2, 2, 1))
            for r in (0, 1) for s in (0, 1)]


def _pool_max(corners):
    a, b, c, d = corners
    return jnp.maximum(jnp.maximum(a, b), jnp.maximum(c, d))


@jax.custom_vjp
def _max_pool_even(x):
    return _pool_max(_pool_corners(x))


def _max_pool_even_fwd(x):
    corners = _pool_corners(x)
    y = _pool_max(corners)
    a, b, c, _ = corners
    winner = jnp.where(a == y, 0, jnp.where(b == y, 1, jnp.where(c == y, 2, 3)))
    return y, winner.astype(jnp.int8)


def _max_pool_even_bwd(winner, g):
    n, h2, w2, c = g.shape
    up = (n, h2, 2, w2, 2, c)          # each pooled cell over its 2×2 window
    corner = (2 * jax.lax.broadcasted_iota(jnp.int8, up, 2)
              + jax.lax.broadcasted_iota(jnp.int8, up, 4))
    dx = jnp.where(jnp.broadcast_to(winner[:, :, None, :, None], up) == corner,
                   jnp.broadcast_to(g[:, :, None, :, None], up),
                   jnp.zeros((), g.dtype))
    return (dx.reshape(n, 2 * h2, 2 * w2, c),)


_max_pool_even.defvjp(_max_pool_even_fwd, _max_pool_even_bwd)


@jax.jit
def max_pool_2x2(x):
    """2×2, stride-2, VALID max-pool of an NHWC array, with the gradient of
    ``jax.lax.reduce_window(x, -inf, max, (1, 2, 2, 1), (1, 2, 2, 1),
    "VALID")`` computed elementwise instead of by select-and-scatter.

    An odd height or width drops its last row or column, as VALID does, and
    those get a zero gradient. Each output's gradient goes to the first
    element of its window, in the order (0,0), (0,1), (1,0), (1,1), whose
    value equals the maximum, as select-and-scatter routes it; every other
    element gets zero. The forward saves that winner as an int8 index at
    pooled resolution, and the backward compares it with each element's
    position in its window. The function is jitted so that an eager caller,
    such as the evaluation, runs the pool as one program instead of
    materialising the four corners of a large activation one by one; inside
    another jit it inlines.
    """
    h, w = x.shape[1] // 2 * 2, x.shape[2] // 2 * 2
    return _max_pool_even(x[:, :h, :w])


def cnn_task(image_hw: int, channels: int, num_classes: int,
             width: int = 16) -> Task:
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        flat = (image_hw // 4) ** 2 * (2 * width)
        return {
            "c1": jax.random.normal(k1, (3, 3, channels, width)) * 0.1,
            "c2": jax.random.normal(k2, (3, 3, width, 2 * width)) * 0.1,
            "w": jax.random.normal(k3, (flat, num_classes)) * (flat ** -0.5),
            "b": jnp.zeros(num_classes),
        }

    def apply(p, x):
        # ReLU then max-pool, written as pool then ReLU: relu(max) equals
        # max(relu) and both send a window's gradient to its first maximum
        # when that is positive and nowhere otherwise, so values and
        # gradients are the same; this order applies the ReLU and its mask
        # at pooled resolution.
        x = x.reshape(x.shape[0], image_hw, image_hw, channels)
        x = jax.lax.conv_general_dilated(
            x, p["c1"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(max_pool_2x2(x))
        x = jax.lax.conv_general_dilated(
            x, p["c2"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(max_pool_2x2(x))
        x = x.reshape(x.shape[0], -1)
        return x @ p["w"] + p["b"]

    return Task("cnn",
                init,
                lambda p, x, y, m: _masked_ce(apply(p, x), y, m),
                lambda p, x, y, m: _masked_acc(apply(p, x), y, m))


# ---------------------------------------------------------------------------
# Tiny transformer LM (paper's Transformer on Wikitext-2 class)
# ---------------------------------------------------------------------------

def lm_task(vocab: int, d: int = 32, seq: int = 16, heads: int = 2) -> Task:
    """Causal 1-layer transformer; x: [B, seq] int tokens, y = x shifted."""
    def init(key):
        ks = jax.random.split(key, 6)
        return {
            "emb": jax.random.normal(ks[0], (vocab, d)) * 0.1,
            "wq": jax.random.normal(ks[1], (d, d)) * d ** -0.5,
            "wk": jax.random.normal(ks[2], (d, d)) * d ** -0.5,
            "wv": jax.random.normal(ks[3], (d, d)) * d ** -0.5,
            "w1": jax.random.normal(ks[4], (d, 4 * d)) * d ** -0.5,
            "w2": jax.random.normal(ks[5], (4 * d, d)) * (4 * d) ** -0.5,
        }

    def apply(p, x):
        h = p["emb"][x]                                   # [B,S,d]
        pos = jnp.arange(x.shape[1])
        q = (h @ p["wq"]).reshape(*x.shape, heads, d // heads)
        k = (h @ p["wk"]).reshape(*x.shape, heads, d // heads)
        v = (h @ p["wv"]).reshape(*x.shape, heads, d // heads)
        s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / (d // heads) ** 0.5
        mask = pos[None, :] <= pos[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        o = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
        h = h + o.reshape(*x.shape, d)
        h = h + jax.nn.relu(h @ p["w1"]) @ p["w2"]
        return h @ p["emb"].T                             # tied unembed

    def loss(p, x, y, m):
        logits = apply(p, x)[:, :-1]
        return _masked_ce(logits, x[:, 1:], m[:, None] *
                          jnp.ones_like(x[:, 1:], jnp.float32))

    def acc(p, x, y, m):
        logits = apply(p, x)[:, :-1]
        return _masked_acc(logits, x[:, 1:], m[:, None] *
                           jnp.ones_like(x[:, 1:], jnp.float32))

    return Task("lm", init, loss, acc)
