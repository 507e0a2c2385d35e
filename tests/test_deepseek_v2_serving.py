"""DeepSeek-V2 on the serving path (ISSUE 51): the engine's own step serving
a LATENT paged cache (one compressed vector and one shared rotary key a
token, read by absorbed attention), a leading dense layer before the expert
layers, a group-limited router of whose experts this "chip" holds one
group, and prefix sharing over the latent pages: held against the plain
reference (`chipbench/reference/deepseek_v2.py`, the NAIVE form) on logits
and on the router's picks, at a toy size that keeps every mechanism: 3
layers (1 dense + 2 expert), hidden 64, 4 heads (nope 16 | rope 8, value
16), query rank 24, latent 32, YaRN with factor 4 over an original context
of 16, 16 experts in 4 groups of which 2 are kept, top-3, a shared expert,
an expert width (1024) that is two blocks of the kernel's width axis (512);
tables of 5 pages, so that the attention kernel's page loop takes its 4
pages a step, and chunks of 16, two of its tiles of 8 tokens.

Engine and reference both compute in float32 here: what separates them is
the order of the sums (absorbed against naive attention, paged online
soft-max against a blocked one, the grouped expert product against a
scan with masks). Logits are O(0.1); the largest difference seen over the
cases below is 3e-7 and LOGIT_ATOL is 10x that; no router pick differs.
"""

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

from paddle_tpu.enforce import EnforceNotMet  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.kernels.pallas import latent_append as LA  # noqa: E402
from paddle_tpu.kernels.pallas import mla_attention as MA  # noqa: E402
from paddle_tpu.kernels.pallas.kv_append import (append_tile,  # noqa: E402
                                                 tile_work)
from paddle_tpu.models import deepseek_v2 as DS  # noqa: E402

from chipbench import weights_deepseek_v2 as WD  # noqa: E402
from chipbench.reference import deepseek_v2 as R  # noqa: E402
from test_falcon_h1_serving import Logits  # noqa: E402

W = dict(vocab_size=96, hidden_size=64, num_layers=3, first_k_dense=1,
         num_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
         qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_ffn=1024,
         num_experts=16, experts_per_tok=3, shared_ffn=64, n_group=4,
         topk_group=2, routed_scaling_factor=2.0, rms_norm_eps=1e-6,
         rope_theta=10000.0, rope_factor=4.0, rope_original_max=16,
         rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale_all_dim=0.707,
         experts_held=(0, 4))
LOGIT_ATOL = 3e-6
ENGINE = dict(max_batch=3, block_size=16, num_blocks=24,
              max_blocks_per_seq=5, chunk=16, decode_burst=4)


def toy_cfg(**kw):
    return DS.DeepseekV2Config(**dict(
        W, dtype=jnp.float32, param_dtype=jnp.float32, **kw))


@pytest.fixture(scope="module")
def params():
    return WD.make_params(W, 3, jnp.float32)


@pytest.fixture(autouse=True)
def width_in_blocks(monkeypatch):
    """The toy expert (64 x 1024 in float32) would fit VMEM twice over and
    be taken whole (`kernels.pallas.moe.WHOLE_F_BYTES`, PR 54); the
    published one (5120 x 1536) does not: keep the toy on the width's two
    blocks, the walk this file is about."""
    from paddle_tpu.kernels.pallas import moe
    monkeypatch.setattr(moe, "WHOLE_F_BYTES", 0)


@pytest.fixture
def logits(monkeypatch):
    return Logits(monkeypatch)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, W["vocab_size"], n,
                                                dtype=np.int32)


def padded(seq, to=16):
    out = np.zeros((-(-len(seq) // to) * to,), np.int32)
    out[:len(seq)] = seq
    return jnp.asarray(out)


def against_reference(params, logits, r, block=16):
    """(largest |engine logit - reference logit| over the served
    positions, (position, layer) pairs whose picks differ from the
    reference's own): the reference is given prompt + served tokens at
    once, in blocks of positions."""
    seq = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
    n = len(seq) - 1
    x, own, _ = R.hidden(params, padded(seq, block), W, block=block)
    want = np.asarray(R.head_logits(params, x))[len(r.prompt) - 1:n]
    got = np.stack(logits.by_rid[r.rid])
    assert got.shape[0] == len(r.output)
    assert (want.argmax(-1) == np.asarray(r.output)).all()
    flips = 0
    if r.routing is not None:
        ran = (r.routing[:n] >= 0).all((1, 2))
        assert ran[r.prefix_hit_tokens:].all() and (r.routing[n:] == -1).all()
        flips = int((np.sort(np.asarray(own)[:n], -1)
                     != np.sort(r.routing[:n], -1)).any(-1)[ran].sum())
    return float(np.abs(got - want).max()), flips


def submit(eng, prompt, new, **kw):
    rid = eng.add_request(prompt, new, keep_routing=True, **kw)
    return next(r for r in eng.queue if r.rid == rid)


# -- (a) engine against reference ------------------------------------------------
def test_chunked_prefill_then_decode_on_logits_and_picks(params, logits):
    """37 prompt tokens in chunks of 16, 16 and 5 against the growing
    latent prefix (the chunk arm), then 10 tokens through the decode arm,
    2 passes a step; a second request joins mid-way in another slot."""
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     **dict(ENGINE, decode_burst=2)))
    a = submit(eng, prompt_of(37), 10)
    for _ in range(2):
        eng.step()
    b = submit(eng, prompt_of(9, 1), 6)
    out = eng.run()
    assert len(out[a.rid]) == 10 and len(out[b.rid]) == 6
    for r in (a, b):
        gap, flips = against_reference(params, logits, r)
        assert gap < LOGIT_ATOL and flips == 0
    assert eng.dispatches == eng.engine_steps      # one program a step
    # the pool is the latent's: two head-less pools, one entry a layer,
    # the dense layer's first; the routing is the two expert layers'
    assert eng.k_pools.shape == (3, 1, 24, 16, 32)
    assert eng.v_pools.shape == (3, 1, 24, 16, 128)     # 8 of 128 lanes
    assert a.routing.shape == (47, 2, 3)
    # padding is not routed: the tokens the router saw are the tokens run
    assert eng.moe_tokens == 2 * ((37 + 9) + (9 + 5))
    assert 0 < eng.moe_local_tokens <= eng.moe_tokens
    assert 0 < eng.moe_experts_touched <= eng.moe_assignments


# -- (b) the share adds up ---------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that every group's chip gives, plus the shared
    expert counted once, are the uncut reference layer: what a chip leaves
    out is exactly what the others hold."""
    uncut = dict(W, experts_held=(0, 16))
    whole = WD.make_params(uncut, 3, jnp.float32)
    f = jnp.asarray(np.random.default_rng(5).standard_normal((23, 64)),
                    jnp.float32)
    p = {k: v[1, 0] for k, v in whole["blocks"][0].items()}
    e = {k: v[1] for k, v in whole["experts"][0].items()}
    with jax.default_matmul_precision("highest"):
        want, own, _ = R.expert_layer(p, e, f, uncut)
        shared = R.gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                             p["shared_down_w"])
        total, picks = -3.0 * shared, []
        for g in range(4):
            cfg = toy_cfg(experts_held=(4 * g, 4 * g + 4))
            held = {k: v[:, 4 * g:4 * g + 4]
                    for k, v in whole["experts"][0].items()}
            y, ids, stats = DS.moe_layer(p, f, held, 1, cfg)
            total = total + y
            picks.append(int(stats[1]))
            assert (np.sort(np.asarray(ids), -1)
                    == np.sort(np.asarray(own), -1)).all()
    assert sum(picks) == 23 * 3             # every pick lies on one chip
    assert float(jnp.abs(total - want).max()) < 2e-6
    # a token's picks lie in at most topk_group groups
    groups = np.asarray(own) // 4
    assert max(len(set(row)) for row in groups) <= 2


# -- (c) the kernels ------------------------------------------------------------------
@pytest.fixture
def aliasing(monkeypatch):
    """The kernels under the interpreter that keeps a TPU's memory: an
    aliased buffer read after it was written gives the NEW value."""
    for mod in (MA, LA):
        monkeypatch.setattr(mod, "_interpret", pltpu.InterpretParams)


def naive_attention(qa, qr, cp, rp, tables, starts, q_lens, kv_lens, scale,
                    layer):
    out = np.zeros(qa.shape, np.float32)
    bs = cp.shape[3]
    for r in range(len(q_lens)):
        for c in range(q_lens[r]):
            pos = kv_lens[r] - q_lens[r] + c
            pages = [tables[r, j] for j in range(pos // bs + 1)]
            kc = np.concatenate([cp[layer, 0, b] for b in pages])[:pos + 1]
            kr = np.concatenate([rp[layer, 0, b] for b in pages])[:pos + 1]
            t = starts[r] + c
            s = (qa[t] @ kc.T + qr[t] @ kr.T) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[t] = (p / p.sum(-1, keepdims=True)) @ kc
    return out


# c_att, q_lens, pos0, then for tables that SHARE leading pages: (rows,
# pages) that take the first of the rows' leading table entries, the size
# of the group each row's last token is then found in, and the pages that
# group attends together (at one page a step of the page loop)
ARMS = {"decode": (1, [1, 0, 1, 1], [5, 0, 16, 47]),
        "chunk": (12, [1, 12, 0, 5], [33, 20, 0, 0]),
        "chunk-ragged-tail": (12, [7, 1, 9, 3], [0, 15, 30, 63]),
        # a group of 2 on three pages (one step and a page of its own at
        # 2 a step) and a group of 3 on two, an off row between them,
        # every context ending mid-page on a tail of its own
        "group-2-and-3": (1, [1, 1, 1, 0, 1, 1], [50, 61, 40, 0, 33, 47],
                          [((0, 1), 3), ((2, 4, 5), 2)],
                          [2, 2, 3, 1, 3, 3], [3, 3, 2, 0, 2, 2]),
        # six rows on one document, four to a group: 4 + 2
        "group-splits": (1, [1, 1, 1, 1, 0, 1, 1],
                         [35, 44, 70, 32, 0, 59, 63],
                         [((0, 1, 2, 3, 5, 6), 2)], [4, 4, 4, 4, 1, 2, 2],
                         [2, 2, 2, 2, 0, 2, 2]),
        # row 2 wrote to its own copy of the third page: the group's
        # members agree on two
        "group-copy-on-write": (1, [1, 1, 1], [66, 52, 79],
                                [((0, 1, 2), 2), ((0, 1), 3)], [3, 3, 3],
                                [2, 2, 2]),
        # a group beside a chunk of 9 (tiles of 4, 4 and its last token, a
        # group of one) in one pass; row 4's only page is its own
        "group-beside-chunk": (12, [1, 9, 1, 0, 1, 1],
                               [37, 30, 45, 0, 34, 7],
                               [((0, 2, 4), 2)], [3, 1, 3, 1, 3, 1],
                               [2, 2, 2, 0, 2, 0])}


@pytest.mark.parametrize("memory,kp", [("copied", 1), ("aliased", 1),
                                       ("aliased", 2)])
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_absorbed_attention_and_the_append_over_ragged_tables(arm, memory,
                                                              kp, request):
    """`mla_paged_attention` against naive attention over the same pages
    (rows of 1 token on the decode arm, chunks in tiles of 4 tokens on the
    chunk arm, rows that are off, contexts that end mid-page, one page and
    two pages a step of the page loop; rows whose tables share their
    leading pages, which the kernel attends together), and
    `latent_append` against a loop, layer 1 of 2."""
    if memory == "aliased":
        request.getfixturevalue("aliasing")
    c_att, q_lens, pos0, *shared = ARMS[arm]
    rng = np.random.default_rng(0)
    L, bs, C, Rd, H, R, nb = 2, 16, 32, 8, 8, len(q_lens), 5
    T = 24 if c_att > 1 else R
    q_lens, pos0 = np.array(q_lens), np.array(pos0)
    kv_lens = pos0 + q_lens
    starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]])
    tables = rng.permutation(np.arange(1, 1 + R * nb)).reshape(R, nb)
    if shared:
        for rows, pages in shared[0]:
            tables[list(rows), :pages] = tables[rows[0], :pages]
        _, _, sizes, steps = MA.decode_groups(tables, q_lens, kv_lens, bs=bs,
                                              kp=kp, cap=4, xp=np)
        assert list(sizes) == shared[1]
        assert list(steps) == [pages // kp for pages in shared[2]]
    cp = rng.standard_normal((L, 1, R * nb + 1, bs, C)).astype(np.float32)
    rp = rng.standard_normal((L, 1, R * nb + 1, bs, Rd)).astype(np.float32)
    qa = rng.standard_normal((T, H, C)).astype(np.float32)
    qr = rng.standard_normal((T, H, Rd)).astype(np.float32)
    dev = [jnp.asarray(a) for a in (tables, starts, q_lens, kv_lens)]
    got = MA.mla_paged_attention(jnp.asarray(qa), jnp.asarray(qr),
                                 jnp.asarray(cp), jnp.asarray(rp), *dev,
                                 0.3, 1, c_att=c_att, tq=4, kp=kp)
    want = naive_attention(qa, qr, cp, rp, tables, starts, q_lens, kv_lens,
                           0.3, 1)
    assert np.abs(np.asarray(got) - want).max() < 2e-6
    c_new = rng.standard_normal((T, C)).astype(np.float32)
    r_new = rng.standard_normal((T, Rd)).astype(np.float32)
    tile = append_tile(jnp.float32, bs)
    work = tile_work(dev[1], jnp.asarray(pos0), dev[2], dev[0], bs=bs,
                     tile=tile, c_att=c_att, T=T)
    c2, r2 = LA.latent_append(jnp.asarray(cp), jnp.asarray(rp),
                              jnp.asarray(c_new), jnp.asarray(r_new), 1,
                              work, tile=tile)
    for r in range(R):
        for c in range(q_lens[r]):
            at = pos0[r] + c
            cp[1, 0, tables[r, at // bs], at % bs] = c_new[starts[r] + c]
            rp[1, 0, tables[r, at // bs], at % bs] = r_new[starts[r] + c]
    assert (np.asarray(c2) == cp).all() and (np.asarray(r2) == rp).all()


def test_the_hosts_count_of_shared_pages_is_the_work_lists():
    """`attn_shared_pages` is counted on the host by the rule the device
    groups by (`decode_groups`, numpy there and jax.numpy here): on random
    tables whose rows share the leading pages of a few documents, some
    after a page of their own, the host's count is the members x shared
    pages of the work list's groups of two or more, and the list's groups
    hold every row of one token once (and a chunk's last tile of one)."""
    rng = np.random.default_rng(3)
    bs, R, nb, kp = 16, 12, 12, MA.KP
    seen = 0
    for _ in range(24):
        tables = rng.permutation(np.arange(1, 1 + R * nb)).reshape(R, nb)
        for rows in np.array_split(rng.permutation(R), 3):   # a document
            pages = rng.integers(0, nb)
            tables[rows, :pages] = tables[rows[0], :pages]
            if pages > 2:       # one row wrote to its copy of a page
                tables[rows[-1], rng.integers(1, pages)] = R * nb + 1
        q_lens = rng.choice([0, 1, 1, 1, 1, 5, 9], R)
        kv_lens = np.where(q_lens > 0, rng.integers(q_lens, nb * bs + 1), 0)
        c_att = int(max(q_lens.max(), 1))
        n, row, c0, n_tok, size, steps, members = (
            np.asarray(a) for a in MA.mla_items(
                jnp.asarray(tables), jnp.asarray(q_lens),
                jnp.asarray(kv_lens), bs=bs, c_att=c_att, T=64))
        group = (np.arange(len(row)) < n[0]) & (n_tok == 1)
        want = int((size * steps * kp)[group & (size > 1)].sum())
        assert MA.shared_pages(tables, q_lens, kv_lens, bs=bs) == want
        held = np.concatenate([members.reshape(len(row), -1)[w, :size[w]]
                               for w in np.flatnonzero(group)] + [[]])
        tile = min(MA.TQ, c_att)    # a chunk's last tile of ONE token
        assert sorted(held) == sorted(np.flatnonzero(
            (q_lens == 1) | ((q_lens > 1) & (q_lens % tile == 1))))
        seen += want
    assert seen > 0


def test_the_work_list_is_made_once_a_pass_not_once_a_layer(params):
    """`ragged_pass` lists latent attention's items before its layer scans
    and every layer's call takes that list: in the step's jaxpr the
    grouping (its one `cumprod`) stands once a PASS (pass 1 and the burst's
    scan body), not once in each of the two layer scans of each."""
    eng = ServingEngine(params, toy_cfg(), **ENGINE)
    eng.add_request(prompt_of(20), 4)
    args = eng._upload_ragged(eng._pack_ragged(eng._admit()))

    def count(jaxpr, name):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == name
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += count(sub, name)
        return n

    jaxpr = jax.make_jaxpr(eng._build_unified(4))(*args).jaxpr
    assert count(jaxpr, "scan") >= 5 and count(jaxpr, "cumprod") == 2


def test_the_blocked_reference_is_the_one_line_form(params):
    """The reference's blocks of positions, its running soft-max and its
    groups of heads against its own one-line attention."""
    rng = np.random.default_rng(2)
    S, h = 48, 4
    qn, kn = (jnp.asarray(rng.standard_normal((S, h, 16)), jnp.float32)
              for _ in range(2))
    qr = jnp.asarray(rng.standard_normal((S, h, 8)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((S, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, h, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = R.attention_dense(qn, qr, kn, kr, v, 0.2)
        got = jnp.concatenate([
            R.attention_blocked(qn[i:i + 16], qr[i:i + 16], kn, kr, v, 0.2,
                                jnp.int32(i), 16) for i in (0, 16, 32)])
    assert float(jnp.abs(got - want).max()) < 2e-6
    seq = padded(prompt_of(40, 7))
    whole = R.forward(params, seq, W)                   # one block of 48
    blocked = R.forward(params, seq, W, block=16)
    assert float(jnp.abs(whole - blocked)[:40].max()) < LOGIT_ATOL
    # YaRN: the program's frequencies are the reference's, and not plain
    cfg = toy_cfg()
    assert np.allclose(DS.yarn_inv_freq(cfg), R.yarn_inv_freq(W), rtol=1e-12)
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    assert not np.allclose(DS.yarn_inv_freq(cfg), plain)
    assert DS.attn_scale(cfg) == pytest.approx(R.attn_scale(W))


# -- (d) prefix sharing over latent pages -------------------------------------------------
def test_prefix_pages_are_shared_copied_on_write_and_found_again(params,
                                                                 logits):
    """A document of 2 pages and a bit: the cold request computes and
    registers its full pages; a request that IS those two pages hits all
    of it while the owner still runs (it recomputes one position into a
    copy of the last shared page: copy-on-write in-program on both latent
    pools); when all have ended the pages are cached-free, refcounts are
    zero, and a later question about the document finds them again. Every
    hit's logits are the reference's, as the cold request's are."""
    eng = logits.watch(ServingEngine(
        params, toy_cfg(), prefix_share=True, pool_audit=True,
        **dict(ENGINE, decode_burst=1, num_blocks=14)))
    doc = prompt_of(40, 11)
    cold = submit(eng, doc, 6)
    while cold.prefill_done < 40:
        eng.step()
    full = submit(eng, doc[:32], 3)             # the two pages, exactly
    out = eng.run()
    assert cold.prefix_hit_tokens == 0 and full.prefix_hit_tokens == 31
    assert eng.cow_copies == 1
    assert (eng.refcount == 0).all()
    assert len(eng._cached_free) == 2           # the document's pages
    ask = submit(eng, np.concatenate([doc[:37], prompt_of(9, 12)]), 5)
    out.update(eng.run())
    assert ask.prefix_hit_tokens == 32 and eng.cache_evictions == 0
    assert eng.prefix_hit_tokens == 31 + 32
    assert eng.prom.get("kv_prefix_hits_total") == 2
    for r in (cold, full, ask):
        gap, flips = against_reference(params, logits, r)
        assert gap < LOGIT_ATOL and flips == 0, (r.rid, gap, flips)
    assert (eng.refcount == 0).all() and len(eng._cached_free) == 2
    # under pressure the cached pages go: 13 pages, 11 of them free; two
    # requests of 5 pages leave 1, the third's 3 take the 2 cached ones
    ended = [submit(eng, prompt_of(n, 13 + i), 4)
             for i, n in enumerate((70, 70, 40))]
    eng.run()
    assert all(r.status == "ok" and len(r.output) == 4 for r in ended)
    assert eng.cache_evictions == 2      # the document is gone
    assert eng.prom.get("kv_prefix_evictions_total") == 2


# -- (e) what it cannot be served with ------------------------------------------------------
@pytest.mark.parametrize("kw,word", [
    ({"int8": True}, "int8 weights"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype"),
    ({"spec_decode_k": 2}, "spec_decode_k"),
    ({"mesh": object()}, "a mesh")])
def test_what_it_cannot_be_served_with_raises_at_construction(params, kw,
                                                              word):
    with pytest.raises(EnforceNotMet, match=word):
        ServingEngine(params, toy_cfg(), **dict(ENGINE, **kw))


def test_the_configuration_refuses_what_the_router_cannot_divide():
    with pytest.raises(EnforceNotMet, match="groups"):
        toy_cfg(n_group=5)
    with pytest.raises(EnforceNotMet, match="whole blocks"):
        DS.DeepseekV2Config(**dict(W, moe_ffn=768))
    assert toy_cfg().head_dim == 24
    # the program's own initialiser makes the tree the seeded maker makes
    mine = jax.eval_shape(
        lambda: DS.init_params(toy_cfg(), jax.random.PRNGKey(0)))
    seeded = jax.eval_shape(lambda: WD.make_params(W, 1, jnp.float32))
    assert jax.tree.map(lambda a: a.shape, mine) == \
        jax.tree.map(lambda a: a.shape, seeded)


# -- one step in flight (ISSUE 31) beside prefix sharing -------------------------------
def test_one_step_in_flight_is_the_synchronous_order_with_hits(params):
    """The same arrivals through `step()` alone and `step(); settle()`:
    a document, then questions about it that arrive while it or each
    other are still in flight and hit its pages (one of them the whole
    of two pages: its copy-on-write rides the next dispatch). Token for
    token the same (the schedules differ by a call where an admission
    waits for a walk): a hit never reads a page the step in flight has
    yet to write, because a page is registered when its step has been
    WALKED."""
    from serving_overlap import both
    doc = prompt_of(40, 21)
    asks = [doc, np.concatenate([doc[:37], prompt_of(6, 22)]), doc[:32],
            np.concatenate([doc, prompt_of(11, 23)])]
    script = {at: [dict(prompt=p, max_new_tokens=n)]
              for at, p, n in zip((0, 3, 4, 6), asks, (9, 7, 5, 8))}

    def make():
        return ServingEngine(params, toy_cfg(), prefix_share=True,
                             pool_audit=True, seed=5,
                             **dict(ENGINE, decode_burst=1))
    flight, sync = both(make, script)
    assert flight.outputs() == sync.outputs()
    assert all(status == "ok" for status, _ in flight.outputs())
    for run in (flight, sync):
        hits = [run.reqs[rid].prefix_hit_tokens for rid in run.order]
        assert hits == [0, 32, 31, 32]
        assert run.eng.cow_copies == 1
        assert (run.eng.refcount == 0).all()
