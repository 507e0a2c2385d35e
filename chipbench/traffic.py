"""The one traffic generator. A traffic mix is a data file of parameters
under `chipbench/traffic/`; this module turns (parameters, seed) into the
inputs of a run. Two kinds today:

`train_tokens`   batches of random token rows for a training step
`closed_loop`    a fixed number of clients, each sending its next request
                 when the last one ends, lengths from a fixed multiset

Every seed offers the same amount of work: the multiset of (prompt, answer)
lengths is a function of the parameters alone, and the seed only orders it
and draws the token ids.
"""

import math

import numpy as np


def _rng(seed, stream):
    return np.random.default_rng([int(seed), stream])


# -- training ---------------------------------------------------------------
def train_batches(params, vocab, seed):
    """`distinct` batches of (tokens, labels), int32 [batch, seq]; every
    row differs. The window cycles through them."""
    rng = _rng(seed, 1)
    shape = (params["distinct"], 2, params["batch"], params["seq"])
    draw = rng.integers(0, vocab, shape, dtype=np.int32)
    return [(b[0], b[1]) for b in draw]


# -- closed loop ------------------------------------------------------------
def _log_grid(lo, hi, n):
    """n lengths on the quantile grid of a log-uniform law on [lo, hi]."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                   ).astype(int)


def length_multiset(params):
    """The fixed multiset of (prompt, answer) pairs: both on their quantile
    grids, paired through a fixed stride so that long prompts meet short
    and long answers alike. The same for every seed."""
    n = params["multiset"]
    prompts = _log_grid(*params["prompt"], n)
    answers = _log_grid(*params["answer"], n)
    stride = params.get("pair_stride", 27)
    assert math.gcd(stride, n) == 1, (stride, n)
    return [(int(prompts[i]), int(answers[(i * stride) % n]))
            for i in range(n)]


class ClosedLoop:
    """Deals requests to `clients` seats. `start_of(i)` is when seat i sends
    its first request, staggered over the first `stagger` share of the
    ramp; after that a seat sends again the moment its answer ends."""

    def __init__(self, params, vocab, seed):
        self.params, self.vocab = params, vocab
        self.clients = params["clients"]
        self.ramp_s = float(params["ramp_s"])
        self._pairs = length_multiset(params)
        self._deal_rng = _rng(seed, 2)
        self._tok_rng = _rng(seed, 3)
        self._first = _rng(seed, 4)
        self._hand = []
        self.dealt = 0

    def start_of(self, seat):
        spread = self.ramp_s * float(self.params["stagger"])
        return spread * seat / self.clients

    def _deal(self):
        """One pass through the multiset, in seeded order. With
        `deal_block` = b the pairs, sorted by prompt length, form b strata,
        and every b consecutive requests take one pair from each stratum:
        any stretch of the run then carries a balanced share of long and
        short prompts, whatever the seed."""
        n, b = len(self._pairs), int(self.params.get("deal_block", 1))
        assert n % b == 0, (n, b)
        ranked = sorted(self._pairs)
        strata = [[ranked[s * (n // b) + i]
                   for i in self._deal_rng.permutation(n // b)]
                  for s in range(b)]
        hand = []
        for j in range(n // b):
            group = [stratum[j] for stratum in strata]
            hand += [group[i] for i in self._deal_rng.permutation(b)]
        return hand[::-1]       # popped from the end

    def next_request(self, seat_first=False):
        """(prompt ids, answer length). A seat's first answer is cut to a
        seeded share of its length, so that seats do not end together."""
        if not self._hand:
            self._hand = self._deal()
        plen, alen = self._hand.pop()
        self.dealt += 1
        if seat_first:
            lo = self.params["answer"][0]
            alen = int(self._first.integers(min(lo, alen), alen + 1))
        prompt = self._tok_rng.integers(0, self.vocab, plen, dtype=np.int32)
        return prompt, alen
