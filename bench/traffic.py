"""The one traffic generator: training batches from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) states the job: sequence
length, global batch, the token stream, how the model is divided over the
chips (``layout``) and the optimizer.  This module reads the stream part.

``affine_stream``: the stream of the program's ``SyntheticLM`` (one
document per sequence), computed without its per-token Python loop.  Each
token follows from the one before by ``x -> (31 x + 7) mod (V - 1) + 1``,
except that with probability ``noise`` it is drawn uniformly from
``[1, V)``.  Row ``b`` of step ``i`` depends only on ``(seed, i)``, drawn in
the same order as ``SyntheticLM``, so the two give the same tokens.
Batches come out in logical order: the program's layout (zigzag over the
context ranks) is applied by ``bench/program.py``.
"""
from __future__ import annotations

import numpy as np

KINDS = ("affine_stream",)


class Stream:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        if traffic["kind"] not in KINDS:
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}; "
                             f"known: {KINDS}")
        self.seq = int(traffic["seq_len"])
        self.batch = int(traffic["global_batch"])
        self.noise = float(traffic["noise"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        m = self.vocab - 1
        # y_{t+k} = (a_k y_t + b_k) mod m for y = x - 1 on a noise-free run
        a = np.empty(self.seq + 1, np.int64)
        b = np.empty(self.seq + 1, np.int64)
        a[0], b[0] = 1, 0
        for k in range(1, self.seq + 1):
            a[k] = a[k - 1] * 31 % m
            b[k] = (b[k - 1] * 31 + 38) % m
        self._a, self._b = a, b

    def logical(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        """(tokens, labels), each (global_batch, seq_len) int32."""
        rng = np.random.default_rng((self.seed, step))
        bsz, s, m = self.batch, self.seq, self.vocab - 1
        first = rng.integers(1, self.vocab, size=bsz)
        noise = rng.random((bsz, s)) < self.noise
        noise_tok = rng.integers(1, self.vocab, size=(bsz, s))
        pos = np.arange(s + 1)
        is_start = np.concatenate([np.ones((bsz, 1), bool), noise], axis=1)
        start_val = np.concatenate([first[:, None], noise_tok], axis=1)
        st = np.maximum.accumulate(np.where(is_start, pos, 0), axis=1)
        y0 = np.take_along_axis(start_val, st, axis=1) - 1
        k = pos[None] - st
        stream = ((self._a[k] * y0 + self._b[k]) % m + 1).astype(np.int32)
        return stream[:, :-1], stream[:, 1:].copy()
