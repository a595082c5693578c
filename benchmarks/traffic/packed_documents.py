"""Training batches as a pre-training job sees them: documents of
heavy-tailed length, tokens drawn from a Zipf law over a vocabulary that
the seed permutes, joined by an end-of-text id and cut into rows of
`seq + 1` tokens (inputs are a row's first `seq`, labels its last `seq`).
The model has no document mask: attention crosses the boundaries, as in
the GPT-3 paper's packing.

Parameters (the traffic file): batch, seq, doc_len_min, doc_len_scale,
doc_len_alpha (Pareto), zipf_s, eot_token, real_vocab.
"""
from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, params: dict, config: dict, seed: int):
        self.p = params
        self.rng = np.random.default_rng([int(seed), 0x7261696E])
        v = int(params.get("real_vocab", config["vocab_size"]))
        self.eot = int(params["eot_token"])
        ranks = np.arange(1, v + 1, dtype=np.float64)
        w = ranks ** -float(params["zipf_s"])
        self.cdf = np.cumsum(w) / np.sum(w)
        ids = np.array([t for t in range(v) if t != self.eot])
        self.perm = self.rng.permutation(ids)
        self.batch, self.seq = int(params["batch"]), int(params["seq"])
        self._buf = np.empty((0,), np.int32)

    def _more(self):
        p = self.p
        n = int(p["doc_len_min"] + p["doc_len_scale"]
                * self.rng.pareto(p["doc_len_alpha"]))
        n = min(n, int(p.get("doc_len_max", 1 << 20)))
        r = np.searchsorted(self.cdf, self.rng.random(n))
        doc = self.perm[np.minimum(r, len(self.perm) - 1)].astype(np.int32)
        self._buf = np.concatenate([self._buf, doc, [np.int32(self.eot)]])

    def next(self):
        need = self.batch * (self.seq + 1)
        while self._buf.shape[0] < need:
            self._more()
        rows = self._buf[:need].reshape(self.batch, self.seq + 1)
        self._buf = self._buf[need:]
        return (np.ascontiguousarray(rows[:, :-1]),
                np.ascontiguousarray(rows[:, 1:]))


def make(params: dict, config: dict, seed: int) -> Batches:
    return Batches(params, config, seed)
