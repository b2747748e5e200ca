"""Deterministic batching iterators for the production LM training driver.

Synthetic token streams (Markov chains) stand in for a real corpus; the
iterator yields {tokens, labels} with labels = tokens (the loss shifts
internally). PRNG streams are derived per (epoch, step) so any batch is
reproducible without global state.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.data.synthetic import make_lm_stream
from repro.telemetry.trace import span


class TokenBatcher:
    """``batch_at(step)`` makes a step's batch on the calling thread, or
    hands over the one ``prefetch(step)`` started on a background thread
    (the training loop starts the next step's batch as it starts the
    current step, so the host makes it while the device computes)."""

    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int = 0):
        self.vocab = vocab
        self.seq_len = seq_len
        self.batch = batch
        self.seed = seed
        self._pool = None
        self._ahead = None                  # (step, future)

    def prefetch(self, step: int):
        if self._pool is None:
            self._pool = ThreadPoolExecutor(1, "batch")
        self._ahead = (step, self._pool.submit(self._make, step))

    def batch_at(self, step: int):
        if self._ahead is not None and self._ahead[0] == step:
            fut, self._ahead = self._ahead[1], None
            return fut.result()
        return self._make(step)

    def _make(self, step: int):
        with span("batch", step=step):
            rng = np.random.default_rng((self.seed, step))
            toks = make_lm_stream(self.batch, self.seq_len,
                                  min(self.vocab, 512), rng)
            return {"tokens": toks.astype(np.int32),
                    "labels": toks.astype(np.int32)}

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1
