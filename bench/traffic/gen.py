"""The benchmark's traffic: one general generator per kind, driven by a
traffic file (``bench/traffic/<name>.json``) and ``--seed``.

* ``serve`` -- open-loop arrivals over ``[0, seconds)``.  The request count
  is fixed, ``round(rate * seconds)``, and arrival times are the sorted
  uniform draws of a Poisson process conditioned on that count.  Classes
  are assigned in their exact weight proportions and generation budgets are
  evenly spaced over ``[gen_min, gen_max]``.  The schedule (arrival times,
  and which request gets which class and budget) is drawn from the mix's
  own ``schedule_seed``, so every ``--seed`` offers the same work at the
  same times; ``--seed`` draws the prompt tokens (and the weights).
* ``train`` -- per-node shifted token streams: node ``k`` draws from its own
  permutation of a Zipf(``zipf_a``) unigram law, and with probability
  ``follow_p`` the next token is ``(prev * 31 + 7) % vocab`` (an order-1
  structure shared by all nodes).  Vectorised over every row of a segment.

Both are copies of the repo's own generators (``repro.serve.traffic`` and
``repro.data.tokens``), reshaped so that the work a seed offers is fixed.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator from any non-negative seed (64 bits and more)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


@dataclasses.dataclass
class ServeRequest:
    rid: int
    cls: str
    prompt: np.ndarray      # (prompt_len,) int32
    max_new: int
    arrival: float          # seconds from the start of the window


def serve_requests(traffic: dict, *, vocab: int, seed: int,
                   seconds: float) -> list[ServeRequest]:
    """The open-loop trace of one run (sorted by arrival)."""
    sched = seed_rng(traffic["schedule_seed"], 1)
    rng = seed_rng(seed, 1)
    n = max(1, int(round(traffic["rate"] * seconds)))
    classes = traffic["classes"]
    w = np.asarray([c["weight"] for c in classes], np.float64)
    counts = np.floor(n * w / w.sum()).astype(int)
    for i in np.argsort(-(n * w / w.sum() - counts))[:n - counts.sum()]:
        counts[i] += 1
    cls_idx = np.repeat(np.arange(len(classes)), counts)
    gens = np.empty(n, np.int64)
    for i, c in enumerate(classes):
        m = counts[i]
        # evenly spaced budgets over [gen_min, gen_max], one set per class
        q = (np.arange(m) + 0.5) / max(m, 1)
        gens[cls_idx == i] = np.round(
            c["gen_min"] + q * (c["gen_max"] - c["gen_min"])).astype(np.int64)
    order = sched.permutation(n)
    cls_idx, gens = cls_idx[order], gens[order]
    arrivals = np.sort(sched.uniform(0.0, seconds, n))
    reqs = []
    for rid in range(n):
        c = classes[cls_idx[rid]]
        prompt = rng.integers(0, vocab, c["prompt_len"], dtype=np.int64)
        reqs.append(ServeRequest(rid=rid, cls=c["name"],
                                 prompt=prompt.astype(np.int32),
                                 max_new=int(gens[rid]),
                                 arrival=float(arrivals[rid])))
    return reqs


def warmup_requests(traffic: dict, *, vocab: int, seed: int,
                    count: int) -> list[ServeRequest]:
    """``count`` requests cycling through every class, two tokens each, all
    due at once: one admission per slot and one prefill per prompt length."""
    rng = seed_rng(seed, 2)
    classes = traffic["classes"]
    return [ServeRequest(
        rid=i, cls=classes[i % len(classes)]["name"],
        prompt=rng.integers(0, vocab, classes[i % len(classes)]["prompt_len"],
                            dtype=np.int64).astype(np.int32),
        max_new=2, arrival=0.0) for i in range(count)]


class TokenFeed:
    """Per-node shifted Zipf/Markov streams; ``segment(i)`` is the stacked
    ``(seg, K, batch, seq + 1)`` int32 batch of segment ``i``, a pure
    function of (seed, i)."""

    def __init__(self, traffic: dict, *, vocab: int, seed: int):
        self.k = traffic["nodes"]
        self.batch = traffic["batch_per_node"]
        self.seq = traffic["seq_len"]
        self.seg = traffic["segment_steps"]
        self.vocab = vocab
        self.seed = seed
        self.follow_p = traffic.get("follow_p", 0.5)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        probs = ranks ** (-traffic.get("zipf_a", 1.2))
        probs /= probs.sum()
        cdf = []
        for k in range(self.k):
            perm = seed_rng(seed, 3, k).permutation(vocab)
            p = np.empty(vocab)
            p[perm] = probs
            cdf.append(np.cumsum(p))
        self._cdf = np.stack(cdf)                      # (K, vocab)

    def segment(self, i: int) -> np.ndarray:
        rng = seed_rng(self.seed, 4, i)
        shape = (self.seg, self.k, self.batch, self.seq + 1)
        u = rng.random(shape)
        tok = np.empty(shape, np.int64)
        for k in range(self.k):
            tok[:, k] = np.minimum(
                np.searchsorted(self._cdf[k], u[:, k], side="right"),
                self.vocab - 1)
        follow = rng.random(shape[:-1] + (self.seq,)) < self.follow_p
        for t in range(self.seq):
            nxt = (tok[..., t] * 31 + 7) % self.vocab
            tok[..., t + 1] = np.where(follow[..., t], nxt, tok[..., t + 1])
        return tok.astype(np.int32)
