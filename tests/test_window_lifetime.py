"""The second page lifetime and the window in the ragged kernel (ISSUE 54),
apart from any model's arithmetic: the kernel under a window against a
dense masked soft-max over ring tables whose other pages are NaN; the
allocator of the window layers' pool (a row's ring never over its bound, a
page freed by step n's walk handed out again from step n+2 and no earlier,
both pools whole when the last request ends, admission blocked by either
pool, a cancelled row's ring given back); `kv_append`'s work list over a
ring, built once a lifetime and pass. The toy model is
`test_trinity_mini_serving.py`'s (a window of 8 at pages of 4 and chunks
of 8: a ring of 6)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.kernels.pallas import ragged_paged_attention as RPA  # noqa: E402
from paddle_tpu.kernels.pallas.kv_append import tile_work  # noqa: E402
from paddle_tpu.models import trinity_mini as TM  # noqa: E402
from paddle_tpu.observability.trace import ADMIT_BLOCKED  # noqa: E402

from test_trinity_mini_serving import (ENGINE, RING, prompt_of,  # noqa: E402
                                       submit, toy_cfg)


@pytest.fixture(scope="module")
def params():
    return TM.init_params(toy_cfg(), jax.random.PRNGKey(0))


# -- the allocator -----------------------------------------------------------
def test_admission_counts_both_pools(params):
    """The head waits for pages of EITHER pool: a window pool that holds
    one row's reservation blocks the second row although the full pool
    has room, and the other way round; when the first row's pages come
    back the second goes in. (Admission alone: no step is compiled.)"""
    for kw in ({"num_window_blocks": RING + 1}, {"num_blocks": 12}):
        eng = ServingEngine(params, toy_cfg(), **dict(ENGINE, **kw))
        a = submit(eng, prompt_of(25), 2)
        b = submit(eng, prompt_of(25, seed=1), 2)
        assert eng._admit() == [0] and a.slot == 0 and b.slot < 0
        assert eng._blocked == ADMIT_BLOCKED.pages
        assert int(eng._wreserved.sum()) == RING    # min(ring, 7 pages)
        eng._release_slot(a)
        assert eng._admit() == [0] and b.slot == 0
        eng._release_slot(b)
        assert eng.free_pages() == eng._num_blocks - 1
        assert eng.free_pages(window=True) == eng._num_wblocks - 1
    # a short request reserves its own pages, not the ring
    eng = ServingEngine(params, toy_cfg(), **ENGINE)
    submit(eng, prompt_of(6), 2)
    eng._admit()
    assert int(eng._wreserved.sum()) == 2
    # a request whose ring no pool of this size ever holds is rejected
    eng = ServingEngine(params, toy_cfg(),
                        **dict(ENGINE, num_window_blocks=RING))
    c = submit(eng, prompt_of(25), 2)
    eng._admit()
    assert c.status == "failed" and "window pool" in c.error


def test_a_freed_page_is_handed_out_again_from_step_n_plus_2(params):
    """With one step in flight (nothing settles): a page waits on the step
    that last read it, is free when that step has been walked, and the
    first pack that takes it is step n+2's; a row's ring never passes its
    bound, and both pools are whole at the end."""
    eng = ServingEngine(params, toy_cfg(), **dict(
        ENGINE, num_window_blocks=4 * RING + 1))    # room: nothing settles
    freed_at, most, again = {}, [0], [0]
    slide, walk, pack = (eng._slide_windows, eng._walk_ragged,
                         eng._pack_ragged)

    def _slide(q_lens, pos0, lens_after):
        before = set(eng.wfree_blocks)
        out = slide(q_lens, pos0, lens_after)
        for page in before - set(eng.wfree_blocks):     # handed out
            if page in freed_at:
                again[0] += 1
                assert eng.engine_steps >= freed_at.pop(page) + 2
        most[0] = max(most[0], int((eng._whi - eng._wlo).max()))
        return out

    def _pack(fresh):
        b = pack(fresh)
        if b is not None:
            b.number = eng.engine_steps
        return b

    def _walk(b, *a):
        for page in b.wfree:    # this walk gives them back
            freed_at[page] = b.number
        return walk(b, *a)
    eng._slide_windows, eng._walk_ragged, eng._pack_ragged = (_slide, _walk,
                                                               _pack)
    reqs = [submit(eng, prompt_of(n, seed=n), new)
            for n, new in ((27, 6), (21, 9), (25, 4))]
    while eng.has_work():
        eng.step()
    assert all(r.status == "ok" for r in reqs)
    assert again[0] > 0 and 4 <= most[0] <= RING
    assert (eng.prom.get("overlap_settles_total", {"reason": "window"})
            or 0) == 0
    assert eng.free_pages() == eng._num_blocks - 1
    assert eng.free_pages(window=True) == eng._num_wblocks - 1


def test_a_cancelled_row_gives_both_pools_back(params):
    eng = ServingEngine(params, toy_cfg(), **dict(ENGINE, preempt=True))
    a = submit(eng, prompt_of(25), 8)
    for _ in range(3):
        eng.step()
    assert eng.free_pages(window=True) < eng._num_wblocks - 1
    eng.cancel(a.rid)
    eng.step()
    assert not eng.has_work()
    assert eng.free_pages() == eng._num_blocks - 1
    assert eng.free_pages(window=True) == eng._num_wblocks - 1
    assert int(eng._wreserved.sum()) == 0


def test_the_work_list_is_made_once_a_lifetime_not_once_a_layer(params):
    """`ragged_pass` builds `kv_append`'s work list once for the full
    table and once for the ring, whatever the number of layers."""
    from paddle_tpu.inference import ragged_step as RS
    eng = ServingEngine(params, toy_cfg(), **ENGINE)
    eng.add_request(prompt_of(10), 4)
    args = eng._upload_ragged(eng._pack_ragged(eng._admit()))
    calls = []
    real = RS.tile_work

    def counted(*a, **kw):
        calls.append(bool(kw.get("ring")))
        return real(*a, **kw)
    RS.tile_work, was = counted, RS.tile_work
    try:
        jax.jit(lambda *a: RS.unified_step(
            *a, cfg=eng.cfg, bs=eng.bs, c_att=eng._c_att, K=1)
        ).lower(*args)
    finally:
        RS.tile_work = was
    assert sorted(calls) == [False, True]


def test_the_ring_work_list_lands_on_the_rings_pages():
    tables = jnp.asarray([[7, 8, 9, 3, 4, 5]], jnp.int32)   # page j -> j % 6
    n, page, sub, tok0, lo, hi = tile_work(
        jnp.asarray([0]), jnp.asarray([26]), jnp.asarray([5]), tables,
        bs=4, tile=4, c_att=8, T=8, ring=True)
    # positions 26-30: pages 6 (entry 0) and 7 (entry 1)
    assert int(n) == 2 and page[:2].tolist() == [7, 8]
    assert lo[:2].tolist() == [2, 0] and hi[:2].tolist() == [4, 3]


# -- the kernel under a window -------------------------------------------------
@pytest.fixture
def aliasing(monkeypatch):
    """The kernel under the interpreter that keeps a TPU's memory."""
    monkeypatch.setattr(RPA, "_interpret", pltpu.InterpretParams)


# c_att, q_lens, pos0 at a window of 8 and pages of 4
ARMS = {
    # one token a row: the window's edge inside a page (pos 13: keys 6-13,
    # page 1 from its third position), at a page's start (pos 11: keys
    # 4-11), a context shorter than the window, an idle row
    "decode": (1, [1, 1, 1, 0, 1], [13, 11, 5, 0, 40]),
    # chunks: one that straddles the edge (its first query's window
    # starts mid-page, its last query's a page later), one from position
    # 0, a ragged tail, a single token beside them
    "chunk": (8, [8, 8, 3, 1], [22, 0, 9, 30]),
    "chunk-odd": (8, [5, 0, 7, 2], [11, 0, 33, 6]),
    # the wide arm's BLOCKS, at two pages (8 positions) a block under a
    # window of 24 (a ring of 10): a chunk whose window starts mid-block
    # (first query at 50 sees 27-50: its row starts at page 6, the pages
    # behind it NaN) and whose blocks are edge, edge, NO edge (40-47:
    # behind every query, inside every window), edge (its own positions),
    # edge (a last page and an unused slice); a chunk from position 0; a ragged
    # chunk with two unmasked blocks; a decode row behind them
    "chunk-blocks": (8, [8, 8, 5, 1], [50, 0, 41, 60])}
# (window, ring, pages a block) where an arm does not take the toy model's
GEOMETRY = {"chunk-blocks": (24, 10, 2)}


def windowed_case(arm, bs=4, hq=4, hkv=2, D=16, layers=2, layer=1):
    c_att, q_lens, pos0 = ARMS[arm]
    window, nbw, _ = GEOMETRY.get(arm, (8, RING, None))
    rng = np.random.default_rng(len(arm))
    R_ = len(q_lens)
    q_lens, pos0 = np.asarray(q_lens), np.asarray(pos0)
    kv_lens = pos0 + q_lens
    T = int(q_lens.sum()) + 3
    starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]])
    NB = 1 + R_ * nbw
    # every page NaN: only what a row's window needs is written
    kp = np.full((layers, hkv, NB, bs, D), np.nan, np.float32)
    vp = np.full_like(kp, np.nan)
    tables = np.zeros((R_, nbw), np.int32)
    keys = rng.normal(size=(R_, int(kv_lens.max()), hkv, D)).astype(np.float32)
    vals = rng.normal(size=keys.shape).astype(np.float32)
    free = list(range(1, NB))
    for r in range(R_):
        if not q_lens[r]:
            continue
        lo = max(pos0[r] - (window - 1), 0) // bs
        for j in range(lo, -(-kv_lens[r] // bs)):
            page = free.pop()
            tables[r, j % nbw] = page
            for s in range(bs):     # the WHOLE page, behind the edge too:
                p = j * bs + s      # the mask has to hide those
                if p < kv_lens[r]:
                    kp[layer, :, page, s] = keys[r, p]
                    vp[layer, :, page, s] = vals[r, p]
                else:
                    kp[layer, :, page, s] = 0.0
                    vp[layer, :, page, s] = 0.0
    q = rng.normal(size=(T, hq, D)).astype(np.float32)
    want = np.zeros_like(q)
    g = hq // hkv
    for r in range(R_):
        for c in range(q_lens[r]):
            i = pos0[r] + c
            js = np.arange(max(i - window + 1, 0), i + 1)
            for h in range(hq):
                s = keys[r, js, h // g] @ q[starts[r] + c, h] * D ** -0.5
                p = np.exp(s - s.max())
                want[starts[r] + c, h] = (p / p.sum()) @ vals[r, js, h // g]
    return (q, kp, vp, tables, starts, q_lens, kv_lens, D ** -0.5, layer,
            c_att, window, want)


@pytest.mark.parametrize("memory", ["copied", "aliased"])
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_the_windowed_kernel_against_a_dense_masked_softmax(arm, memory,
                                                            request):
    """Both arms of the kernel under a window, over a ring table, every
    page the window does not need NaN: a page read that should not be, or
    a key behind the edge that is not masked, shows in the output."""
    if memory == "aliased":
        request.getfixturevalue("aliasing")
    if arm in GEOMETRY:
        request.getfixturevalue("monkeypatch").setattr(
            RPA, "_BLOCK_PAGES", GEOMETRY[arm][2])
    (q, kp, vp, tables, starts, q_lens, kv_lens, scale, layer, c_att, window,
     want) = windowed_case(arm)
    got = RPA.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(starts, jnp.int32),
        jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
        scale, None, None, jnp.int32(layer), c_att=c_att, window=window)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6)
    # and without the window the same call reads what the window hides
    if arm == "decode":
        bare = RPA.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
            scale, None, None, jnp.int32(layer), c_att=c_att)
        assert not np.allclose(np.nan_to_num(np.asarray(bare)), want,
                               atol=1e-3)




def test_the_host_counts_the_wide_arms_blocks_as_the_kernel_takes_them(
        monkeypatch):
    """`wide_arm_pages` (the dispatch span's `chunk_pages` and
    `chunk_masked_pages`) by hand. Trinity-Mini's geometry: 8 query heads a
    KV head, chunks of 256, pages of 128 x 128 bf16, so 8 pages a block.
    A chunk that ends at 8,192: 64 pages without a window, of which the
    last block's 8 hold its own positions; under the window of 2,048 it
    starts at page 46 and walks 18 pages: the first block's 8 (the trailing
    edge), none of the second's, the last 2. Decode rows take the other arm.
    Then the toy case above, block by block."""
    trinity = dict(hq=32, hkv=4, bs=128, D=128, itemsize=2, c_att=256)
    assert RPA.wide_arm_pages([256, 1, 0], [8192, 5000, 0],
                              **trinity) == (64, 8)
    assert RPA.wide_arm_pages([256, 1, 0], [8192, 5000, 0], window=2048,
                              **trinity) == (18, 10)
    # a burst pass holds one token a row: 8 folded rows, the narrow arm
    assert RPA.wide_arm_pages([1, 1], [70, 9000], **dict(trinity, c_att=1)) \
        == (0, 0)
    c_att, q_lens, pos0 = ARMS["chunk-blocks"]
    window, _, pages = GEOMETRY["chunk-blocks"]
    monkeypatch.setattr(RPA, "_BLOCK_PAGES", pages)
    toy = dict(hq=4, hkv=2, bs=4, D=16, itemsize=4, c_att=c_att,
               window=window)
    kv_lens = np.add(q_lens, pos0)
    # row 0: pages 6-14, all but 10-11 in an edge block; row 1: its two
    # pages; row 2: pages 4-11, the first and the last block
    assert RPA.wide_arm_pages(q_lens, kv_lens, **toy) == (9 + 2 + 8,
                                                          7 + 2 + 4)
