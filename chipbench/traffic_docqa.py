"""Traffic of kind `closed_loop_docqa`: many short questions about a few long
documents, context caching on. The documents are made once (`documents`),
prefilled once in set-up and their pages shared; a request is (one
document + a question, an answer length).

Every seed offers the same amount of work: the documents' lengths (the
quantile grid of a log-uniform law) and the multiset of (document,
question length, answer length) triples are functions of the parameters
alone; the seed draws the token ids and the order of the deal. In the
multiset every document appears `multiset / documents` times, and a deal
takes it in groups of `documents` requests that hold EVERY document once
(`deal_block` = the number of documents), in seeded order: any stretch of
a run attends over the same resident context, whatever the seed.

`DocQA` answers as `traffic.ClosedLoop` does (`clients`, `start_of`,
`next_request`), so `runners.serve_closed.Loop` drives it.
"""

import math

import numpy as np

from chipbench.traffic import _log_grid, _rng


def document_lengths(params):
    return [int(n) for n in _log_grid(*params["document"],
                                      params["documents"])]


def triple_multiset(params):
    """The fixed multiset of (document, question length, answer length):
    questions and answers on their quantile grids, met through two fixed
    strides so that every document meets short and long ones of both."""
    n, d = params["multiset"], params["documents"]
    assert n % d == 0, (n, d)
    questions = _log_grid(*params["question"], n)
    answers = _log_grid(*params["answer"], n)
    sq, sa = params["question_stride"], params["answer_stride"]
    assert math.gcd(sq, n) == 1 and math.gcd(sa, n) == 1, (sq, sa, n)
    return [(i % d, int(questions[(i * sq) % n]), int(answers[(i * sa) % n]))
            for i in range(n)]


def documents(params, vocab, seed):
    """The documents' token ids, from the seed."""
    rng = _rng(seed, 6)
    return [rng.integers(0, vocab, n, dtype=np.int32)
            for n in document_lengths(params)]


class DocQA:
    """Deals (document + question, answer length) to `clients` seats."""

    def __init__(self, params, vocab, seed, docs):
        self.params, self.vocab, self.docs = params, vocab, docs
        self.clients = params["clients"]
        self.ramp_s = float(params["ramp_s"])
        self._triples = triple_multiset(params)
        assert int(params["deal_block"]) == len(docs), params["deal_block"]
        self._deal_rng = _rng(seed, 2)
        self._tok_rng = _rng(seed, 3)
        self._first = _rng(seed, 4)
        self._hand = []
        self.dealt = 0
        self.document_of = []   # of each request dealt, in order

    def start_of(self, seat):
        spread = self.ramp_s * float(self.params["stagger"])
        return spread * seat / self.clients

    def _deal(self):
        """One pass through the multiset: groups of one triple a document,
        the document's triples in seeded order, a group in seeded order."""
        d = len(self.docs)
        per = len(self._triples) // d
        strata = [[t for t in self._triples if t[0] == doc] for doc in
                  range(d)]
        strata = [[s[i] for i in self._deal_rng.permutation(per)]
                  for s in strata]
        hand = []
        for j in range(per):
            group = [s[j] for s in strata]
            hand += [group[i] for i in self._deal_rng.permutation(d)]
        return hand[::-1]       # popped from the end

    def next_request(self, seat_first=False):
        """(prompt ids = document + question, answer length). A seat's
        first answer is cut to a seeded share of its length, so that seats
        do not end together."""
        if not self._hand:
            self._hand = self._deal()
        doc, qlen, alen = self._hand.pop()
        self.dealt += 1
        self.document_of.append(doc)
        if seat_first:
            lo = self.params["answer"][0]
            alen = int(self._first.integers(min(lo, alen), alen + 1))
        question = self._tok_rng.integers(0, self.vocab, qlen,
                                          dtype=np.int32)
        return np.concatenate([self.docs[doc], question]), alen
