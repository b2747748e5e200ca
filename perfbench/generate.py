"""The one traffic generator: every traffic mix is a file of parameters
(``perfbench/traffic/<name>.json``) that a function here reads by its
``kind``. Data takes the place of a served request stream: it is made
anew from ``--seed`` in every run.

The image generator copies ``repro.data.synthetic``'s class templates and
Dirichlet label skew. It fixes what sets the compiled shapes and the work
of a round (per-worker dataset sizes, padded length) from the traffic's
own ``sizes_seed``, so every ``--seed`` runs the same programs on other
data. The token generator copies ``repro.data.loader.TokenBatcher``'s
Markov streams.
"""
from __future__ import annotations

import numpy as np


def prng_seed(seed: int) -> int:
    """A ``--seed`` of any size as the 32-bit seed of a JAX PRNG key."""
    return int(seed) % (1 << 32)


def worker_sizes(traffic: dict, workers: int):
    """Per-worker dataset sizes |D_i| (the paper's Assumption 3.1 spread),
    drawn from the traffic's fixed ``sizes_seed``; and the padded length."""
    rng = np.random.default_rng(traffic["sizes_seed"])
    n, spread = traffic["n_per_worker"], traffic["size_spread"]
    caps = n * (1 + spread * (2 * rng.random(workers) - 1))
    sizes = np.maximum(8, caps.astype(np.int64))
    return sizes, int(n * (1 + spread))


def federated_images(traffic: dict, workers: int, task: dict, seed: int,
                     test_size: int):
    """Non-iid image data for ``workers`` workers: x [W, N, hw*hw*c],
    y [W, N], mask [W, N], sizes [W], and ``test_size`` iid test images
    test_x, test_y."""
    rng = np.random.default_rng(seed)
    hw, ch, classes = task["hw"], task["channels"], task["num_classes"]
    sizes, nmax = worker_sizes(traffic, workers)
    yy, xx = np.meshgrid(np.linspace(-1, 1, hw), np.linspace(-1, 1, hw))
    templates = []
    for _ in range(classes):
        fx, fy = rng.uniform(0.5, 3.0, 2)
        ph = rng.uniform(0, 2 * np.pi, 2)
        t = np.sin(fx * np.pi * xx + ph[0]) * np.cos(fy * np.pi * yy + ph[1])
        templates.append(np.stack([t] * ch, -1))
    templates = np.stack(templates).reshape(classes, -1).astype(np.float32)

    def draw(y):
        noise = rng.normal(size=(y.size, templates.shape[1]))
        return (templates[y] + traffic["noise"] * noise).astype(np.float32)

    x = np.zeros((workers, nmax, templates.shape[1]), np.float32)
    ys = np.zeros((workers, nmax), np.int32)
    mask = np.zeros((workers, nmax), np.float32)
    for w in range(workers):
        props = rng.dirichlet([traffic["alpha"]] * classes)
        y = rng.choice(classes, size=int(sizes[w]), p=props).astype(np.int32)
        x[w, :y.size], ys[w, :y.size], mask[w, :y.size] = draw(y), y, 1.0
    test_y = rng.integers(0, classes, size=test_size) \
        .astype(np.int32)
    return {"x": x, "y": ys, "mask": mask, "sizes": sizes,
            "test_x": draw(test_y), "test_y": test_y}


def markov_tokens(rng: np.random.Generator, rows: int, seq: int, vocab: int):
    """Markov-chain token rows with a learnable bigram structure."""
    trans = rng.dirichlet([0.1] * vocab, size=vocab).cumsum(axis=1)
    out = np.empty((rows, seq), np.int32)
    state = rng.integers(0, vocab, size=rows)
    for t in range(seq):
        out[:, t] = state
        u = rng.random((rows, 1))
        state = (trans[state] > u).argmax(axis=1)
    return out


def token_batch(traffic: dict, rows: int, seq: int, vocab: int, seed: int,
                step: int):
    """Step ``step``'s [rows, seq] token batch, fresh at every step as
    ``TokenBatcher.batch_at`` makes it. Token ids are drawn from the first
    ``traffic["markov_vocab"]`` ids of the model's vocabulary."""
    vocab = min(vocab, traffic["markov_vocab"])
    return markov_tokens(np.random.default_rng((seed, step)), rows, seq,
                         vocab)
