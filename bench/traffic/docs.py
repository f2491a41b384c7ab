"""Token-document traffic: each row of a stream is one user's endless run of
documents, from the run's seed (a mix file `bench/traffic/<mix>.json` of
kind "documents").

  length_median, length_sigma  document lengths are log-normal, median and
                               sigma of the log
  length_min, length_max       ... clipped to this range (tokens)
  zipf                         token ids: Zipf with this exponent over the
                               model's whole vocabulary, the ranks
                               permuted by the seed

`DocStream(mix, seed, batch, vocab)` is step-keyed: `stream(t) -> (x_t
int32 [B, 2], y_t int32 [B])`, x_t[:, 0] the token, x_t[:, 1] 1 where the
token starts a document (the row resets there), y_t the row's next token.
A row's documents are drawn by (seed, row, document), so any step can be
replayed without the ones before it.
"""
from __future__ import annotations

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


class DocStream:
    def __init__(self, mix: dict, seed: int, batch: int, vocab: int):
        if mix.get("kind") != "documents":
            raise ValueError(f"not a document mix: {mix.get('kind')!r}")
        self.mix, self.seed, self.B, self.V = mix, int(seed), batch, vocab
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -float(mix["zipf"]))
        self.cdf = cdf / cdf[-1]
        self.perm = _rng(self.seed, 7).permutation(vocab).astype(np.int32)
        self.starts = [[0] for _ in range(batch)]   # document start steps
        self._doc = {}                               # row -> (d, tokens)

    def length(self, row: int, d: int) -> int:
        m = self.mix
        z = _rng(self.seed, 1, row, d).standard_normal()
        n = np.exp(np.log(float(m["length_median"]))
                   + float(m["length_sigma"]) * z)
        return int(np.clip(np.rint(n), m["length_min"], m["length_max"]))

    def _locate(self, row: int, t: int) -> tuple[int, int]:
        """(document index, offset in it) of step t in a row."""
        s = self.starts[row]
        while s[-1] <= t:
            s.append(s[-1] + self.length(row, len(s) - 1))
        d = int(np.searchsorted(s, t, side="right")) - 1
        return d, t - s[d]

    def tokens(self, row: int, d: int) -> np.ndarray:
        if self._doc.get(row, (None,))[0] != d:
            n = self.starts[row][d + 1] - self.starts[row][d]
            u = _rng(self.seed, 2, row, d).random(n)
            ranks = np.minimum(np.searchsorted(self.cdf, u), self.V - 1)
            self._doc[row] = (d, self.perm[ranks])
        return self._doc[row][1]

    def token(self, row: int, t: int) -> tuple[int, int]:
        d, off = self._locate(row, t)
        return int(self.tokens(row, d)[off]), int(off == 0)

    def __call__(self, t: int):
        x = np.zeros((self.B, 2), np.int32)
        y = np.zeros((self.B,), np.int32)
        for b in range(self.B):
            x[b] = self.token(b, t)
            y[b] = self.token(b, t + 1)[0]
        return x, y


def window(stream, start: int, steps: int) -> dict:
    """tokens, resets, targets [steps, B] of steps start .. start+steps-1."""
    xs, ys = zip(*(stream(start + i) for i in range(steps)))
    xs = np.stack(xs)
    return {"tokens": xs[..., 0], "resets": xs[..., 1],
            "targets": np.stack(ys)}
