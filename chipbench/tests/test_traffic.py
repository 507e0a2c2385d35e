"""The traffic generator: every seed offers the same work in another
order."""

import collections

import numpy as np

from chipbench import harness, traffic as T

DOCS = harness.load_json("traffic", "docs.json")
CHAT = harness.load_json("traffic", "chat.json")


def test_multiset_is_on_the_stated_ranges_and_fixed():
    for mix in (DOCS, CHAT):
        pairs = T.length_multiset(mix)
        assert len(pairs) == mix["multiset"] == 64
        assert pairs == T.length_multiset(dict(mix))
        (plo, phi), (alo, ahi) = mix["prompt"], mix["answer"]
        assert all(plo <= p <= phi and alo <= a <= ahi for p, a in pairs)
        # every prompt quantile meets an answer quantile of its own
        assert len({a for _, a in pairs}) > 40
        assert max(p + a for p, a in pairs) <= 16 * 128


def _deal(mix, seed, n):
    gen = T.ClosedLoop(mix, 50304, seed)
    return [gen.next_request() for _ in range(n)]


def test_same_multiset_for_every_seed_another_deal():
    want = collections.Counter(T.length_multiset(CHAT))
    deals = {}
    for seed in (0, 7, 2 ** 31 + 12345):
        reqs = _deal(CHAT, seed, 128)
        for lo in (0, 64):      # every 64 requests carry the same work
            got = collections.Counter((len(p), a) for p, a in reqs[lo:lo + 64])
            assert got == want
        deals[seed] = [(len(p), a) for p, a in reqs]
        assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < 50304
                   for p, _ in reqs)
    assert deals[0] != deals[7] != deals[2 ** 31 + 12345]
    # stratified: every 8 consecutive requests take one prompt from each
    # eighth of the sorted multiset
    ranked = sorted(T.length_multiset(CHAT))
    eighth = {pair: i // 8 for i, pair in enumerate(ranked)}
    assert CHAT["deal_block"] == 8 and len(eighth) == 64
    for deal in deals.values():
        for lo in range(0, 128, 8):
            assert sorted(eighth[p] for p in deal[lo:lo + 8]) == list(range(8))
    again = _deal(CHAT, 7, 128)
    assert [(len(p), a) for p, a in again] == deals[7]
    assert all((a[0] == b[0]).all() for a, b in zip(again, _deal(CHAT, 7, 128)))


def test_ramp_and_stagger():
    gen = T.ClosedLoop(DOCS, 50304, 3)
    starts = [gen.start_of(s) for s in range(gen.clients)]
    assert starts[0] == 0 and starts == sorted(starts)
    assert max(starts) < DOCS["ramp_s"] * DOCS["stagger"]
    # a seat's first answer is cut short, never longer than dealt
    first = T.ClosedLoop(DOCS, 50304, 3)
    plain = T.ClosedLoop(DOCS, 50304, 3)
    for _ in range(32):
        (p1, a1), (p2, a2) = first.next_request(True), plain.next_request()
        assert len(p1) == len(p2) and DOCS["answer"][0] <= a1 <= a2


def test_train_batches_rows_all_differ():
    mix = harness.load_json("traffic", "seq2k-b4.json")
    a = T.train_batches(mix, 50304, 11)
    b = T.train_batches(mix, 50304, 11)
    c = T.train_batches(mix, 50304, 12)
    assert len(a) == mix["distinct"]
    rows = np.concatenate([np.concatenate(pair) for pair in a])
    assert rows.shape == (4 * 2 * mix["batch"], mix["seq"])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()


class _Engine:
    """A toy engine: a request takes `answer` steps once admitted."""

    def __init__(self):
        self.live, self.peak = {}, 0
        self.prom = self

    def get(self, name):
        return float(len(self.live))

    def add_request(self, prompt, answer, on_token):
        rid = len(self.live) + getattr(self, "_n", 0)
        self._n = getattr(self, "_n", 0) + 1
        self.live[self._n] = [answer, on_token]
        self.peak = max(self.peak, len(self.live))
        return self._n

    def step(self):
        ended = []
        for rid, item in list(self.live.items()):
            item[0] -= 1
            item[1](rid, 0)
            if item[0] == 0:
                del self.live[rid]
                ended.append(type("R", (), {"rid": rid, "status": "ok"})())
        return ended


def test_closed_loop_never_exceeds_its_clients():
    from chipbench.runners.serve_closed import Loop
    mix = dict(CHAT, ramp_s=0.0)
    eng = _Engine()
    loop = Loop(eng, T.ClosedLoop(mix, 50304, 5))
    for _ in range(600):
        loop.step()
    assert eng.peak == mix["clients"]
    assert len(loop.submitted) > 2 * mix["clients"]
    # a seat sends again only after its answer ended
    by_seat = collections.defaultdict(list)
    for made, rid in loop.submitted:
        by_seat[loop.seat_of[rid]].append(rid)
    ended = {r.rid for _, r in loop.finished}
    assert all(rid in ended for rids in by_seat.values() for rid in rids[:-1])
