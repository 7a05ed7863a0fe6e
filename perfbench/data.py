"""Inputs from the seed: training token streams and served request lists.

Everything here is a pure function of the traffic file's parameters and
``--seed``; the program receives only what is generated.
"""

from __future__ import annotations

import time

import numpy as np


class TokenStream:
    """One node's training data: a seeded token stream cut into windows of
    ``block`` tokens at any offset, the interface ``Trainer`` asks of a
    dataset (``__len__``, ``take(idx) -> (x, y)``).

    Tokens are drawn with a skewed unigram distribution (``vocab * u**3``
    for uniform ``u``) so that a model has something to learn and the loss
    must fall below ``ln(vocab)``, while no two windows are equal. Every
    ``take`` is recorded: the reference later follows the very rows the
    program was fed.
    """

    def __init__(self, seed: int, node: int, vocab: int, block: int,
                 tokens: int):
        rng = np.random.default_rng([int(seed), int(node), 0x7a])
        u = rng.random(int(tokens), dtype=np.float32)
        self.data = np.minimum((vocab * u ** 3).astype(np.int32), vocab - 1)
        self.block = int(block)
        self.taken: list = []       # (idx, x, y) of the first takes
        self.take_times: list = []  # host clock of every take

    def __len__(self) -> int:
        return self.data.shape[0] - self.block - 1

    def take(self, idx):
        idx = np.asarray(idx, np.int64)
        win = idx[:, None] + np.arange(self.block + 1)[None, :]
        rows = self.data[win]
        x, y = rows[:, :-1].copy(), rows[:, 1:].copy()
        self.take_times.append(time.monotonic())
        if len(self.taken) < 16:
            self.taken.append((idx.copy(), x, y))
        return x, y

    def __getitem__(self, i):
        x, y = self.take(np.array([i]))
        return x[0], y[0]

    def step_batches(self, steps: int):
        """The ``(x, y)`` of the first ``steps`` training steps. ``fit``
        draws one example batch at index 0 to shape its state before the
        first step; a take of nothing but index 0 is that and is left
        out."""
        real = [(x, y) for idx, x, y in self.taken
                if not (len(idx) > 1 and not idx.any())]
        if len(real) < steps:
            raise RuntimeError(
                f"only {len(real)} batches were drawn, {steps} wanted")
        return real[:steps]


def _lognormal_grid(n: int, median, sigma, lo, hi):
    """``n`` lengths at the log-normal's quantiles ``(i + 0.5) / n``,
    clipped: the distribution's shape without a draw's luck."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def closed_requests(traffic: dict, vocab: int, seed: int, count: int):
    """The seeded list a closed loop's clients draw from, in order:
    ``count`` requests, each ``{"prompt", "max_new_tokens", "seed",
    "greedy"}``.

    Every seed gets the same set of sizes in another order: the list is
    made of blocks of ``block_of`` requests; each block holds the same
    ``block_of`` prompt lengths and the same output lengths (the clipped
    log-normals' quantiles), paired and ordered by the seed. So whichever
    stretch of the list a window reaches, it holds the same work to within
    one block. Token ids and each request's sampling seed come from the
    seed; every ``greedy_every``-th request decodes greedily (``top_k=1``)
    so that its tokens can be judged against the reference."""
    rng = np.random.default_rng([int(seed), 0xc105ed])
    p, o, n = traffic["prompt_tokens"], traffic["output_tokens"], \
        int(traffic["block_of"])
    p_grid = _lognormal_grid(n, p["median"], p["sigma"], p["min"], p["max"])
    o_grid = _lognormal_grid(n, o["median"], o["sigma"], o["min"], o["max"])
    every = int(traffic["greedy_every"])
    out = []
    while len(out) < count:
        plen, olen = rng.permutation(p_grid), rng.permutation(o_grid)
        for j in range(n):
            i = len(out)
            out.append({
                "prompt": rng.integers(0, vocab, plen[j]).tolist(),
                "max_new_tokens": int(olen[j]),
                "seed": int(rng.integers(0, 2 ** 31 - 1)),
                "greedy": i % every == every - 1,
            })
    return out[:count]


def first_round_cut(traffic: dict, seed: int, clients: int):
    """Each client's FIRST request has its output length cut to a share of
    its draw, so that completions are spread from the first round on and
    no run starts in lock-step. The shares are an even grid from
    ``first_request_min_share`` to 1, dealt to the clients by the seed."""
    rng = np.random.default_rng([int(seed), 0xf125])
    lo = float(traffic.get("first_request_min_share", 0.1))
    return rng.permutation(lo + (1.0 - lo) * (np.arange(clients) + 0.5)
                           / clients)


def prompt_bucket(n: int, block: int) -> int:
    """The power-of-two prefill bucket of an ``n``-token prompt."""
    return min(1 << (max(n, 1) - 1).bit_length(), block)
