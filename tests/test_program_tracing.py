"""The program names its own work (ISSUE 26): host spans with parent and
attributes that reach any jax.profiler trace, the spans inside one ragged
serving step, `jax.named_scope`s inside the three compiled programs, a
name on every Pallas kernel, and the benchmark's readers of all that on
slices recorded on the TPU v5e. Since ISSUE 38 also a request's life as one
record (four parts that sum to its TTFT, two instant spans inside the walk,
four back-dated collector events), the scheduler's choices on the
admission and dispatch spans, a span that takes attributes until it
closes, and the two readers of span attributes."""

import ast
import functools
import glob
import gzip
import importlib
import json
import os
import re
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.models import gpt as G  # noqa: E402
from paddle_tpu.models import falcon_h1 as FH  # noqa: E402
from paddle_tpu.models import qwen3_next as QN  # noqa: E402
from paddle_tpu.models import deepseek_v2 as DS  # noqa: E402
from paddle_tpu.observability.trace import (ADMISSION_ATTRS,  # noqa: E402
                                            ADMIT_BLOCKED, COMPILE_CACHE,
                                            COMPILE_SPANS, DISPATCH_ATTRS,
                                            CHUNK_DISPATCH_ATTRS,
                                            FIRST_CALL_ATTRS,
                                            FIRST_TOKEN_ATTRS, KERNELS,
                                            LATENT_DISPATCH_ATTRS,
                                            MOE_FETCH_ATTRS,
                                            MOE_LOCAL_FETCH_ATTRS,
                                            REQUEST_END_ATTRS,
                                            REQUEST_PHASES, REQUEST_SPANS,
                                            SCOPES, SERVING_SPANS,
                                            SSM_DISPATCH_ATTRS,
                                            STARTUP_SPANS,
                                            WINDOW_DISPATCH_ATTRS)
from paddle_tpu.profiler.utils import RecordEvent, collector  # noqa: E402

from chipbench import harness, program_trace  # noqa: E402

DATA = os.path.join(REPO, "tests", "data")


def tiny_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=64, dtype=jnp.float32)
    base.update(kw)
    return G.GPTConfig(**base)


# -- the span primitive -----------------------------------------------------
def test_a_span_reaches_a_jax_profiler_trace_without_the_collector(tmp_path):
    """Whoever starts the profiler session, the program's spans are in its
    file, attributes as the event's stats."""
    assert not collector.enabled
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with RecordEvent("outer_probe", step=3, k=2):
            with RecordEvent("inner_probe", kv_tokens=12345678901):
                jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    host = {h[0]: h for h in program_trace.from_xplane(files[0])["host"]}
    assert host["outer_probe"][3] == {"step": 3, "k": 2}
    assert host["inner_probe"][3] == {"kv_tokens": 12345678901}
    o, i = host["outer_probe"], host["inner_probe"]
    assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]


def test_parent_and_attributes_are_recorded_and_nest(tmp_path):
    with obs.capture_spans() as cap:
        with obs.span("a", rows=4) as a:
            with obs.span("b"):
                with obs.span("c", note="x"):
                    pass
            with obs.span("b2"):
                pass
        with obs.span("lone"):
            pass
    ev = {e.name: e for e in cap.events}
    assert ev["a"].parent is None and ev["lone"].parent is None
    assert ev["b"].parent == ev["a"].span_id == ev["b2"].parent
    assert ev["c"].parent == ev["b"].span_id
    assert ev["a"].attrs == {"rows": 4} and ev["c"].attrs == {"note": "x"}
    assert len({e.span_id for e in cap.events}) == 5
    assert a.name == "a"
    path = obs.write_chrome_trace(str(tmp_path / "t.json"), cap.events)
    args = {e["name"]: e["args"] for e in
            json.load(open(path))["traceEvents"]}
    assert args["c"] == {"span_id": ev["c"].span_id,
                         "parent": ev["b"].span_id, "note": "x"}
    assert args["a"]["rows"] == 4 and args["a"]["parent"] is None


def test_begin_end_pairs_that_do_not_nest_keep_the_stack_sound():
    with obs.capture_spans() as cap:
        a, b = RecordEvent("a"), RecordEvent("b")
        a.begin()
        b.begin()
        a.end()          # ends before its child
        b.end()
        with RecordEvent("after"):
            pass
    ev = {e.name: e for e in cap.events}
    assert ev["b"].parent == ev["a"].span_id
    assert ev["after"].parent is None


# -- the serving step -------------------------------------------------------
def _expected_dispatch(eng):
    """The dispatch attributes one step should carry, from the engine's
    state before it (no speculation, budget never binds)."""
    n_dec = n_pre = q_tokens = 0
    rows = []       # (kv positions after pass 1, samples, may still emit)
    new = []        # ... and how many of those positions pass 1 adds
    for r in eng.slots:
        if r is None:
            continue
        if r.prefill_done >= len(r.prompt):
            n_dec += 1
            q_tokens += 1
            new.append(1)
            rows.append((int(eng.lens[r.slot]) + 1, True,
                         r.max_new_tokens - len(r.output)))
        else:
            n_pre += 1
            grant = min(eng.chunk, len(r.prompt) - r.prefill_done)
            q_tokens += grant
            new.append(grant)
            done = r.prefill_done + grant >= len(r.prompt)
            rows.append((r.prefill_done + grant, done,
                         r.max_new_tokens - len(r.output) if done else 0))
    return n_dec, n_pre, q_tokens, rows, new


def test_one_ragged_step_yields_every_serving_span_once():
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=4,
                        block_size=16, num_blocks=16, chunk=8,
                        decode_burst=4)
    eng.add_request(np.arange(5) % 64, max_new_tokens=12)
    eng.add_request(np.arange(7) % 64, max_new_tokens=12)
    for _ in range(2):
        eng.step()                      # both rows decode from here on
    eng.add_request(np.arange(20) % 64, max_new_tokens=4)
    eng.step()                          # the newcomer is admitted
    # reading `slots` settles: the expectation is taken from settled state
    n_dec, n_pre, q_tokens, rows, new = _expected_dispatch(eng)
    assert n_dec == 2 and n_pre == 1
    micro0 = eng.decode_microsteps
    with obs.capture_spans() as first:
        eng.step()      # nothing in flight: dispatches, fetches nothing
    k = eng.decode_microsteps - micro0
    with obs.capture_spans() as cap:
        eng.step()      # dispatches the next step, THEN lands that one
    # (a step that builds a variant also hands the session that start-up
    # work: the compile events and the first-call span)
    assert {e.event_type for e in first.events} <= {"UserDefined", "Compile",
                                                    "Startup"}
    assert [e.name for e in first.events
            if e.event_type == "UserDefined"] == [
        SERVING_SPANS.sweep, SERVING_SPANS.admission, SERVING_SPANS.pack,
        SERVING_SPANS.upload, SERVING_SPANS.dispatch, SERVING_SPANS.metrics,
        SERVING_SPANS.step]
    by_name = {}
    for e in cap.events:
        by_name.setdefault(e.name, []).append(e)
    assert set(SERVING_SPANS) <= set(by_name)
    assert all(len(by_name[n]) == 1 for n in SERVING_SPANS)
    step = by_name[SERVING_SPANS.step][0]
    children = [by_name[n][0] for n in SERVING_SPANS
                if n != SERVING_SPANS.step]
    assert all(c.parent == step.span_id for c in children)
    assert all(step.start <= c.start and c.end <= step.end
               for c in children)
    order = sorted(children, key=lambda c: c.start)
    assert [c.name for c in order] == [
        SERVING_SPANS.sweep, SERVING_SPANS.admission, SERVING_SPANS.pack,
        SERVING_SPANS.upload, SERVING_SPANS.dispatch, SERVING_SPANS.fetch,
        SERVING_SPANS.walk, SERVING_SPANS.metrics]
    assert all(a.end <= b.start for a, b in zip(order, order[1:]))
    assert sum(c.duration for c in children) >= 0.95 * step.duration
    assert by_name[SERVING_SPANS.dispatch][0].attrs["in_flight"] == 1
    attrs = [e.attrs for e in first.events
             if e.name == SERVING_SPANS.dispatch][0]
    assert tuple(attrs) == DISPATCH_ATTRS + CHUNK_DISPATCH_ATTRS
    kv = sum(end for end, _, _ in rows) + sum(
        end + j for j in range(1, k) for end, samples, left in rows
        if samples and left > j)
    pages = sum(-(-end // 16) for end, _, _ in rows) + sum(
        -(-(end + j) // 16) for j in range(1, k)
        for end, samples, left in rows if samples and left > j)
    # the append's tiles (8 rows of f32 here): a row of pass 1 those its
    # new positions lie in, a row alive in a burst pass one
    tiles = sum((end - 1) // 8 - (end - q) // 8 + 1
                for (end, _, _), q in zip(rows, new)) + sum(
        1 for j in range(1, k) for _, samples, left in rows
        if samples and left > j)
    assert attrs == {"step": eng.engine_steps - 1, "k": k, "n_dec": n_dec,
                     "n_pre": n_pre, "q_tokens": q_tokens, "kv_tokens": kv,
                     "attn_pages": pages, "kv_tiles": tiles, "in_flight": 0,
                     "n_starved": 0,
                     "pre_tokens": q_tokens - n_dec,
                     "budget": eng.token_budget,
                     # MHA and a chunk of 8: every row fits the kernel's
                     # 8-row arm, nothing takes the wide one
                     "chunk_pages": 0, "chunk_masked_pages": 0}
    # the admission span closes with what it did: nobody waited
    adm = [e.attrs for e in cap.events if e.name == SERVING_SPANS.admission]
    assert adm == [dict(zip(ADMISSION_ATTRS,
                            (0, 0, ADMIT_BLOCKED.none, 0)))]


def test_attn_pages_counts_the_row_page_pairs_of_a_hand_built_step():
    """`attn_pages` is what the attention kernel walks: for every row that
    runs, the pages its context fills after the pass (16-token pages
    here). With `k`, the batch and the table width it gives the share of
    the kernel's (row, page) slots that carry work. `kv_tiles` is what
    the in-place append walks: the 8-row tiles (a float32 pool's) a row's
    NEW positions lie in."""
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=4,
                        block_size=16, num_blocks=16, chunk=8,
                        decode_burst=1)
    eng.add_request(np.arange(5) % 64, max_new_tokens=12)    # row A
    eng.add_request(np.arange(20) % 64, max_new_tokens=4)    # row B
    seen = []
    for _ in range(4):
        with obs.capture_spans() as cap:
            eng.step()
        seen += [(e.attrs["kv_tokens"], e.attrs["attn_pages"],
                  e.attrs["kv_tiles"])
                 for e in cap.events if e.name == SERVING_SPANS.dispatch]
    # contexts after each pass, A / B: 5 / 7 (the token budget is 12),
    # 6 / 15, 7 / 20, 8 / 21 -- B crosses into its second page on the
    # third step; its chunks 7..14 and 15..19 each lie in two tiles
    assert seen == [(12, 2, 2), (21, 2, 3), (27, 3, 3), (29, 3, 2)]


def tiny_pattern_cfg():
    return QN.Qwen3NextConfig(
        vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
        num_experts=8, experts_per_tok=2, moe_ffn=16, shared_ffn=16,
        experts_held=(0, 4), ssm_chunk=8, dtype=jnp.float32,
        param_dtype=jnp.float32)


def tiny_latent_cfg(num_layers):
    return DS.DeepseekV2Config(
        vocab_size=64, hidden_size=32, num_layers=num_layers, num_heads=2,
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=48, moe_ffn=16,
        num_experts=8, experts_per_tok=2, shared_ffn=16, n_group=4,
        topk_group=2, experts_held=(0, 2), rope_original_max=16,
        dtype=jnp.float32, param_dtype=jnp.float32)


def test_attn_shared_pages_counts_what_two_rows_on_one_document_share():
    """A latent cache's attention attends the leading pages that decode
    rows' tables share ONCE for those rows (`mla_attention`'s group item),
    and `attn_shared_pages` says how many of the step's `attn_pages` went
    that way. By hand: a document of 4 pages (16-token pages, the kernel's
    4 pages a step) and a bit; its owner decodes while a question about it
    arrives, hits the 4 pages and decodes beside it: 2 rows x 4 pages a
    pass that both run, and nothing while either is alone or prefilling."""
    cfg = tiny_latent_cfg(num_layers=2)
    params = DS.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, max_blocks_per_seq=8, chunk=40,
                        decode_burst=2, prefix_share=True)
    doc = np.arange(70) % 64
    eng.add_request(doc, max_new_tokens=12)
    seen = []

    def steps(n):
        for _ in range(n):
            with obs.capture_spans() as cap:
                eng.step()
            eng.settle()
            seen.extend((e.attrs["k"], e.attrs["n_dec"], e.attrs["attn_pages"],
                         e.attrs["attn_shared_pages"]) for e in cap.events
                        if e.name == SERVING_SPANS.dispatch)

    steps(3)        # 40 + 30 prompt tokens, then the owner decodes alone
    eng.add_request(np.concatenate([doc[:64], np.arange(5)]),
                    max_new_tokens=6)
    steps(4)
    assert eng.prefix_hit_tokens == 64
    # (k, decode rows, attn_pages, attn_shared_pages): the owner's chunks
    # (3 and 5 pages), its two passes alone at 71 and 72 positions, the
    # question's 5 tokens beside it, then both rows on their fifth page,
    # two passes a step; in the last the question has one token left, so
    # the second pass has the owner alone
    assert seen == [(1, 0, 3, 0), (1, 0, 5, 0), (2, 1, 10, 0), (1, 1, 10, 0),
                    (2, 2, 20, 16), (2, 2, 20, 16), (2, 2, 15, 8)]


def test_an_idle_step_keeps_its_spans():
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2,
                        block_size=16, num_blocks=16, chunk=8)
    with obs.capture_spans() as cap:
        eng.step()
    assert [e.name for e in cap.events] == [
        SERVING_SPANS.sweep, SERVING_SPANS.admission, SERVING_SPANS.pack,
        SERVING_SPANS.metrics, SERVING_SPANS.step]


def test_the_two_unread_prom_counters_are_gone():
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2,
                        block_size=16, num_blocks=16, chunk=8)
    eng.add_request(np.arange(4) % 64, max_new_tokens=2)
    eng.run(max_steps=10)
    text = eng.metrics_text()
    assert "engine_steps_total" in text
    assert "prefill_slots_total" not in text
    assert "decode_slots_total" not in text


# -- names inside the compiled programs -------------------------------------
def _scopes_in(lowered):
    """Scopes on the name stacks of the lowered text: a path component
    (`.../embed/...`, `jvp(mlp)`), never the bare quoted `"ssm_conv"(` of a
    Python frame. A jitted jnp helper keeps the traceback of its first
    trace in the process, so a function named like a scope (`ssm_conv`,
    `ssm_scan`) shows up in another program's locations when its tests ran
    first in the same worker."""
    text = lowered.as_text(debug_info=True)
    return {s for s in SCOPES
            if re.search(r'[/(]%s[/)"]|"%s[/)]' % (s, s), text)}


TRAIN_SCOPES = {SCOPES.embed, SCOPES.attn, SCOPES.qkv, SCOPES.flash,
                SCOPES.attn_out, SCOPES.mlp, SCOPES.head_loss,
                SCOPES.optimizer}


def test_the_dense_step_carries_its_scopes():
    cfg = tiny_cfg(max_seq_len=32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    state = opt.init_state(params)

    def step(params, state, tokens, labels):     # the benchmark's step
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, 1e-3)
        return params, state, loss
    tok = jnp.zeros((2, 16), jnp.int32)
    assert _scopes_in(jax.jit(step).lower(params, state, tok, tok)) == \
        TRAIN_SCOPES


def test_the_hybrid_step_carries_its_scopes_and_its_axes():
    cfg = tiny_cfg(max_seq_len=32)
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2},
                           devices=jax.devices()[:8])
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, paddle.optimizer.AdamW(learning_rate=1e-3),
        num_microbatches=2)
    params = shard_params(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    state = init_state(params)
    tok = jnp.zeros((4, 16), jnp.int32)
    found = _scopes_in(step.lower(params, state, tok, tok,
                                  jnp.float32(1e-3)))
    assert found == TRAIN_SCOPES | {SCOPES.coll_mp, SCOPES.coll_dp,
                                    SCOPES.coll_pp}


def test_the_unified_serving_step_carries_its_scopes():
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2,
                        block_size=16, num_blocks=16, chunk=8,
                        decode_burst=4, prefix_share=True)
    eng.add_request(np.arange(6) % 64, max_new_tokens=8)
    batch = eng._pack_ragged(eng._admit())
    lowered = eng._build_unified(2).lower(*eng._upload_ragged(batch))
    assert _scopes_in(lowered) == {
        SCOPES.embed, SCOPES.qkv, SCOPES.kv_write, SCOPES.ragged_attn,
        SCOPES.proj_mlp, SCOPES.head, SCOPES.sample, SCOPES.cow,
        SCOPES.burst}


def test_the_hybrid_serving_step_carries_its_scopes_and_attributes():
    """Falcon-H1 through the same engine: the GPT step's scopes (no `cow`:
    prefix sharing is refused), the mixer's four and `rope`; the dispatch
    span gains the three state attributes after the seven it had."""
    cfg = FH.FalconH1Config(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_hidden=64, ssm_heads=4,
        ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=8,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = FH.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2,
                        block_size=16, num_blocks=16, chunk=8,
                        decode_burst=4)
    eng.add_request(np.arange(6) % 64, max_new_tokens=8)
    batch = eng._pack_ragged(eng._admit())
    lowered = eng._build_unified(2).lower(*eng._upload_ragged(batch))
    assert _scopes_in(lowered) == {
        SCOPES.embed, SCOPES.qkv, SCOPES.rope, SCOPES.kv_write,
        SCOPES.ragged_attn, SCOPES.ssm_in, SCOPES.ssm_conv, SCOPES.ssm_scan,
        SCOPES.ssm_out, SCOPES.proj_mlp, SCOPES.head, SCOPES.sample,
        SCOPES.burst}
    with obs.capture_spans() as cap:
        eng.step()
    attrs = [e.attrs for e in cap.events
             if e.name == SERVING_SPANS.dispatch][0]
    assert tuple(attrs) == (DISPATCH_ATTRS + CHUNK_DISPATCH_ATTRS
                            + SSM_DISPATCH_ATTRS)
    assert (attrs["ssm_scan_rows"], attrs["ssm_update_rows"],
            attrs["ssm_tokens"]) == (1, 0, 6)


def test_the_pattern_serving_step_carries_its_scopes_and_attributes():
    """Qwen3-Next through the same engine: the linear layers' four
    scopes, the expert layer's three, attention's of the GPT step (no
    `cow`); the dispatch span carries the state attributes, and the
    fetch span the router's counts of the step landed before it."""
    cfg = tiny_pattern_cfg()
    params = QN.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, chunk=8, decode_burst=4)
    eng.add_request(np.arange(6) % 64, max_new_tokens=8)
    batch = eng._pack_ragged(eng._admit())
    lowered = eng._build_unified(2).lower(*eng._upload_ragged(batch))
    assert _scopes_in(lowered) == {
        SCOPES.embed, SCOPES.qkv, SCOPES.rope, SCOPES.kv_write,
        SCOPES.ragged_attn, SCOPES.gdn_in, SCOPES.gdn_conv, SCOPES.gdn_scan,
        SCOPES.gdn_out, SCOPES.moe_route, SCOPES.moe_experts,
        SCOPES.moe_shared, SCOPES.proj_mlp, SCOPES.head, SCOPES.sample,
        SCOPES.burst,
        # not a scope here: the conv KERNEL's name, which follows
        # `gdn_conv` on its path (the cell's shares do not list it)
        SCOPES.ssm_conv}
    with obs.capture_spans() as cap:
        eng.run()
    disp = [e.attrs for e in cap.events if e.name == SERVING_SPANS.dispatch]
    assert tuple(disp[0]) == (DISPATCH_ATTRS + CHUNK_DISPATCH_ATTRS
                              + SSM_DISPATCH_ATTRS)
    fetch = [e.attrs for e in cap.events if e.name == SERVING_SPANS.fetch]
    assert all(tuple(a) == MOE_FETCH_ATTRS for a in fetch)
    # every landed step's counts ride the fetch that landed it: the first
    # fetch has the first step's, and the fetches together are the totals
    assert fetch[0]["moe_assignments"] > 0
    assert sum(a["moe_assignments"] for a in fetch) == eng.moe_assignments
    assert sum(a["moe_experts_touched"] for a in fetch) == \
        eng.moe_experts_touched
    assert all(a["moe_experts_touched"] <= a["moe_assignments"]
               and a["moe_load_max"] <= a["moe_assignments"] for a in fetch)
    assert tiles_hold_their_rows(fetch) == {16}


def test_the_latent_serving_step_carries_its_scopes_and_attributes():
    """DeepSeek-V2 through the same engine, prefix sharing on: latent
    attention's two scopes in place of `qkv`, `rope` and `ragged_attn`,
    the expert layer's three, `proj_mlp` for the leading dense layer's
    FFN, `cow` for the copy-on-write; the dispatch span carries the prefix
    hit and the chunk's (query, key) pairs, the fetch span the router's
    counts and the group-limited router's two more."""
    cfg = tiny_latent_cfg(num_layers=3)
    params = DS.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, chunk=8, decode_burst=2,
                        prefix_share=True)
    eng.add_request(np.arange(20) % 64, max_new_tokens=4)
    batch = eng._pack_ragged(eng._admit())
    lowered = eng._build_unified(2).lower(*eng._upload_ragged(batch))
    assert _scopes_in(lowered) == {
        SCOPES.embed, SCOPES.mla_proj, SCOPES.kv_write, SCOPES.mla_attn,
        SCOPES.moe_route, SCOPES.moe_experts, SCOPES.moe_shared,
        SCOPES.proj_mlp, SCOPES.head, SCOPES.sample, SCOPES.cow,
        SCOPES.burst}
    with obs.capture_spans() as cap:
        eng.run()
        eng.add_request(np.arange(24) % 64, max_new_tokens=3)   # a hit
        eng.run()
    disp = [e.attrs for e in cap.events if e.name == SERVING_SPANS.dispatch]
    assert all(tuple(a) == DISPATCH_ATTRS + LATENT_DISPATCH_ATTRS
               for a in disp)
    assert sum(a["prefix_hit_tokens"] for a in disp) == 16 == \
        eng.prefix_hit_tokens
    # the first step ran 8 prompt tokens from position 0: 8 * 9 / 2 pairs,
    # of which kv_tokens counts the row's 8
    assert disp[0]["kv_tokens"] + disp[0]["chunk_ctx_tokens"] == 36
    fetch = [e.attrs for e in cap.events if e.name == SERVING_SPANS.fetch]
    assert all(tuple(a) == MOE_FETCH_ATTRS + MOE_LOCAL_FETCH_ATTRS
               for a in fetch)
    assert sum(a["moe_tokens"] for a in fetch) == eng.moe_tokens == \
        2 * ((20 + 3) + (8 + 2))
    assert sum(a["moe_local_tokens"] for a in fetch) == eng.moe_local_tokens
    assert tiles_hold_their_rows(fetch) == {16}


def test_the_windowed_serving_step_carries_its_scopes_and_attributes():
    """Trinity-Mini through the same engine: `window_attn` beside
    `ragged_attn` (the one kernel under a window and without), `qkv` and
    `rope`, the expert layer's three, `proj_mlp`; the dispatch span
    carries what the window layers read and the pages the pack gave back,
    the fetch span the router's counts."""
    from paddle_tpu.models import trinity_mini as TM
    cfg = TM.TrinityMiniConfig(
        vocab_size=64, hidden_size=32, num_layers=5, num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=8, sliding_window=8,
        intermediate_size=48, num_experts=8, experts_per_tok=2, moe_ffn=16,
        shared_ffn=16, experts_held=(0, 8), dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = TM.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=4,
                        num_blocks=16, chunk=8, decode_burst=2)
    eng.add_request(np.arange(21) % 64, max_new_tokens=4)
    batch = eng._pack_ragged(eng._admit())
    lowered = eng._build_unified(2).lower(*eng._upload_ragged(batch))
    assert _scopes_in(lowered) == {
        SCOPES.embed, SCOPES.qkv, SCOPES.rope, SCOPES.kv_write,
        SCOPES.ragged_attn, SCOPES.window_attn, SCOPES.moe_route,
        SCOPES.moe_experts, SCOPES.moe_shared, SCOPES.proj_mlp, SCOPES.head,
        SCOPES.sample, SCOPES.burst}
    eng._release_slot(eng._slots[0])    # the hand-packed step never ran
    eng.add_request(np.arange(21) % 64, max_new_tokens=4)
    with obs.capture_spans() as cap:
        eng.run()
    disp = [e.attrs for e in cap.events if e.name == SERVING_SPANS.dispatch]
    assert all(tuple(a) == (DISPATCH_ATTRS + CHUNK_DISPATCH_ATTRS
                            + WINDOW_DISPATCH_ATTRS) for a in disp)
    # the first step ran 8 prompt tokens from position 0: every layer
    # reads the row's 8 positions (1 full + 4 window layers), 2 pages
    assert disp[0]["kv_tokens"] == 8 and disp[0]["kv_layer_tokens"] == 40
    assert disp[0]["win_attn_pages"] == 2 and disp[0]["win_pages_freed"] == 0
    # a chunk of 8 on two query heads a KV head takes the kernel's wide arm
    # (a decode row its 8-row arm), and at these toy pages one block of
    # pages holds a row's whole context, so every page it walks is in a
    # masked block: 2 pages in each of the 5 layers
    assert (disp[0]["chunk_pages"], disp[0]["chunk_masked_pages"]) == (10, 10)
    # the third (positions 16-20) sees 7 + 5 positions under the window
    # and 21 without; the page behind the window went back at its pack
    assert disp[2]["kv_tokens"] == 21 and disp[2]["win_pages_freed"] >= 1
    assert disp[2]["k"] == 1 and disp[2]["kv_layer_tokens"] == 21 + 4 * 12
    # ... 6 pages in the full layer, pages 2-5 in each window layer
    assert disp[2]["chunk_pages"] == 6 + 4 * 4 == disp[2]["chunk_masked_pages"]
    assert all(a["chunk_pages"] == 0 for a in disp if a["n_pre"] == 0)
    assert sum(a["win_pages_freed"] for a in disp) == eng.window_pages_freed
    fetch = [e.attrs for e in cap.events if e.name == SERVING_SPANS.fetch]
    assert all(tuple(a) == MOE_FETCH_ATTRS for a in fetch)
    assert sum(a["moe_experts_touched"] for a in fetch) == \
        eng.moe_experts_touched
    assert tiles_hold_their_rows(fetch) == {16}
    assert eng.free_pages(window=True) == eng._num_wblocks - 1


def _toy_routed_engine(model, **kw):
    """A toy engine of the plain-routed or the group-limited model."""
    if model == "q3n":
        cfg = tiny_pattern_cfg()
        params = QN.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg = tiny_latent_cfg(num_layers=3)
        params = DS.init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(params, cfg, max_batch=2, block_size=16,
                         num_blocks=16, **kw)


def tiles_hold_their_rows(fetch):
    """What the fetch spans' tile attributes must satisfy whatever was
    routed: a real tile holds one expert's rows, at least one and at most
    the height, and the height is whole sublane tiles up to the MXU's
    rows. Returns the heights."""
    for a in fetch:
        assert a["moe_experts_touched"] <= a["moe_tiles"] \
            <= a["moe_assignments"] <= a["moe_tiles"] * a["moe_tile_rows"]
        assert a["moe_tile_rows"] in range(16, 129, 16)
    return {a["moe_tile_rows"] for a in fetch}


def test_the_fetch_span_carries_the_tiles_walked():
    """A routed model's `serving_fetch` span says how many real tiles the
    grouped expert product walked in the step it landed and how tall they
    were, so rows a tile (`moe_assignments / moe_tiles`) and the tiles'
    fill (that over `moe_tile_rows`) can be read from any trace. The
    height follows the pass's static shape: 32 where pass 1 packs 128
    positions x top-2 over 8 experts (the toys of the three
    `..._carries_its_scopes_and_attributes` tests read 16). The
    group-limited router's two counts still come from their own columns:
    every position is routed in each of the two expert layers."""
    from paddle_tpu.kernels.pallas import moe
    eng = _toy_routed_engine("dsv2", max_blocks_per_seq=12, chunk=126,
                             decode_burst=1)
    assert moe.tile_rows(eng.token_budget, 2, 8) == 32
    eng.add_request(np.arange(140) % 64, max_new_tokens=3)
    with obs.capture_spans() as cap:
        eng.run()
    fetch = [e.attrs for e in cap.events if e.name == SERVING_SPANS.fetch]
    assert all(tuple(a) == MOE_FETCH_ATTRS + MOE_LOCAL_FETCH_ATTRS
               for a in fetch)
    assert tiles_hold_their_rows(fetch) == {32}
    # the held group's two experts cannot fill more than their tiles
    assert fetch[0]["moe_tiles"] <= 2 * (2 + 128 * 2 // 32)
    assert sum(a["moe_tokens"] for a in fetch) == 2 * (140 + 2)
    assert 0 < sum(a["moe_local_tokens"] for a in fetch) == \
        eng.moe_local_tokens <= eng.moe_tokens


def test_the_router_counts_are_read_by_column_name():
    """`_note_routing` finds a count by the name the model's
    `route_stats` gives its column, never by the vector's width: the five
    columns of `moe.pass_stats` are no group-limited router's, and the
    group-limited router's two come after them."""
    plain = _toy_routed_engine("q3n", chunk=8)
    grouped = _toy_routed_engine("dsv2", chunk=8)
    assert grouped.model.route_stats[-2:] == ("local_tokens", "tokens")
    # [K = 2 passes, L layers, columns]: touched, assignments, load_max,
    # tiles, tile_rows (, local_tokens, tokens); the second pass is idle
    row = np.asarray([3, 40, 20, 5, 16, 7, 9], np.int32)
    for eng in (plain, grouped):
        L, width = eng._routed_layers, len(eng.model.route_stats)
        stats = np.zeros((2, L, width), np.int32)
        stats[0] = row[:width]
        attrs = eng._note_routing(stats)
        want = dict(zip(MOE_FETCH_ATTRS, (3 * L, 40 * L, 20, 5 * L, 16)))
        if eng is grouped:
            want.update(zip(MOE_LOCAL_FETCH_ATTRS, (7 * L, 9 * L)))
        assert attrs == want and eng.moe_passes == 1
        assert (eng.moe_experts_touched, eng.moe_assignments) == (3 * L,
                                                                  40 * L)
    assert (grouped.moe_local_tokens, grouped.moe_tokens) == (
        7 * grouped._routed_layers, 9 * grouped._routed_layers)


# -- a request's life, and the scheduler's choices (ISSUE 38) ----------------
def test_a_span_takes_attributes_until_it_closes(tmp_path):
    """`RecordEvent.set` merges into the span's attributes and reaches a
    jax.profiler session's stats; after `end()` it does nothing."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.capture_spans() as cap:
            with RecordEvent("late_probe", step=3) as ev:
                jnp.ones((4,)).block_until_ready()
                ev.set(landed=7, why="pages")
                ev.set(landed=8)
            ev.set(after=1)
    finally:
        jax.profiler.stop_trace()
    assert ev.attrs == {"step": 3, "landed": 8, "why": "pages"}
    assert [e.attrs for e in cap.events
            if e.event_type != "Compile"] == [ev.attrs]
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = {h[0]: h for h in program_trace.from_xplane(files[0])["host"]}
    assert host["late_probe"][3] == {"step": 3, "landed": 8, "why": "pages"}
    idle = RecordEvent("never_begun")
    idle.set(x=1)                       # not open: nothing to set
    assert idle.attrs == {}


@pytest.fixture(scope="module")
def mixed_engine(tmp_path_factory):
    """Four requests through three slots, nine pages of 8 and a budget of
    ONE 8-token chunk a step, preemption on: `victim` decodes long and is
    evicted for the queue's head; `starved` is resident from the start but
    waits for budget behind the victim's chunk; `queued` arrives to a pool
    with too few pages; `quick` (4 tokens in, 1 out) is done a step pair
    after its chunk. Returns (collector events, {name: Request}, the
    engine, the path of the event log it wrote to)."""
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    log = obs.EventLog(str(tmp_path_factory.mktemp("mixed") / "ev.jsonl"))
    before = obs.set_event_log(log)
    try:
        with obs.capture_spans() as build:
            eng = ServingEngine(
                params, cfg, max_batch=3, block_size=8, num_blocks=10,
                chunk=8, token_budget=8, decode_burst=2, preempt=True,
                preempt_wait_steps=1)
        events, done = _mixed_arrivals(eng)
    finally:
        obs.set_event_log(before)
    return build.events + events, done, eng, log.path


@pytest.fixture(scope="module")
def mixed_run(mixed_engine):
    return mixed_engine[:2]


def _mixed_arrivals(eng):
    names, done = {}, {}

    def add(name, n_prompt, n_new):
        names[eng.add_request(np.arange(n_prompt) % 64,
                              max_new_tokens=n_new)] = name
    with obs.capture_spans() as cap:
        add("victim", 8, 24)
        add("starved", 20, 4)
        for _ in range(2):
            eng.step()
        add("queued", 20, 4)
        add("quick", 4, 1)
        for _ in range(200):
            if not eng.has_work():
                break
            for r in eng.step():
                done[names[r.rid]] = r
    assert sorted(done) == sorted(names.values())
    return cap.events, done


@pytest.mark.parametrize("name", ["victim", "starved", "queued", "quick"])
def test_the_four_parts_are_non_negative_and_sum_to_the_ttft(mixed_run,
                                                              name):
    events, done = mixed_run
    r = done[name]
    parts = r.ttft_parts()
    assert len(parts) == 4 and all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(r.ttft_s, abs=1e-9)
    assert r.ttft_s == r.first_token_time - r.submit_time
    # what makes each of the four the case it stands for
    if name == "victim":
        assert r.preemptions == 1 and r.prefill_steps > 1   # re-prefilled
    elif name == "starved":
        assert r.starved_steps > 0
        assert parts[1] > parts[0]          # waited for budget, not a slot
    elif name == "queued":
        assert parts[0] > 0 and r.starved_steps == 0
    else:
        assert (len(r.output), r.decode_steps, r.prefill_steps) == (1, 0, 1)
    (ft,) = [e for e in events if e.name == REQUEST_SPANS.first_token
             and e.attrs["rid"] == r.rid]
    a = ft.attrs
    assert tuple(a) == FIRST_TOKEN_ATTRS
    us = [a["queue_us"], a["wait_us"], a["prefill_us"], a["land_us"]]
    assert all(u >= 0 for u in us)
    assert sum(us) == round(r.ttft_s * 1e6)         # to the microsecond
    assert all(abs(u - p * 1e6) <= 1 for u, p in zip(us, parts))
    assert a["prompt_len"] == len(r.prompt) - r.folded
    assert a["land_steps"] == 1         # one step of pipeline depth
    assert a["preemptions"] == 0        # nobody is evicted before a token


def test_one_first_token_and_one_end_a_request_inside_the_walk(mixed_run):
    events, done = mixed_run
    walks = {e.span_id for e in events if e.name == SERVING_SPANS.walk}
    for span, attrs in ((REQUEST_SPANS.first_token, FIRST_TOKEN_ATTRS),
                        (REQUEST_SPANS.end, REQUEST_END_ATTRS)):
        seen = [e for e in events if e.name == span]
        assert sorted(e.attrs["rid"] for e in seen) == \
            sorted(r.rid for r in done.values())
        assert all(tuple(e.attrs) == attrs for e in seen)
        assert all(e.parent in walks for e in seen)
    for e in events:
        if e.name != REQUEST_SPANS.end:
            continue
        (r,) = [r for r in done.values() if r.rid == e.attrs["rid"]]
        assert e.attrs == dict(zip(REQUEST_END_ATTRS, (
            r.rid, "ok", len(r.output), r.decode_steps,
            e.attrs["total_us"], r.preemptions)))
        assert e.attrs["total_us"] >= round(r.ttft_s * 1e6)
    assert done["victim"].decode_steps >= 12    # 23 tokens, bursts of 2


@pytest.mark.parametrize("name", ["victim", "starved", "queued", "quick"])
def test_the_request_phases_share_rid_and_tile_the_way_to_the_token(
        mixed_run, name):
    events, done = mixed_run
    r = done[name]
    bars = [e for e in events if e.name in set(REQUEST_PHASES)
            and e.attrs == {"rid": r.rid}]
    assert [e.name for e in bars] == list(REQUEST_PHASES)
    assert all(e.parent is None for e in bars)
    assert bars[0].start == r.submit_time
    assert bars[-1].end == r.first_token_time
    assert all(a.end == b.start for a, b in zip(bars, bars[1:]))
    assert [e.duration for e in bars] == list(r.ttft_parts())


def _by_id(events):
    return {e.span_id: e for e in events}


def test_the_first_dispatch_of_a_variant_is_one_first_call_span(mixed_engine):
    """The engine's construction is one `startup_engine_build`; every
    variant the scheduler chose opens ONE `startup_program_first_call`,
    inside the dispatch span of its first step, with its burst size and
    the trace, lowering and backend compile that ended inside it; no
    later dispatch opens one."""
    events, _, eng, _ = mixed_engine
    assert [e.event_type for e in events
            if e.name == STARTUP_SPANS.engine] == ["Startup"]
    first = [e for e in events if e.name == STARTUP_SPANS.program]
    assert sorted((e.attrs["k"], bool(e.attrs["spec"])) for e in first) == \
        sorted(eng._unified_cache)
    dispatches = [e for e in events if e.name == SERVING_SPANS.dispatch]
    assert len(dispatches) > len(first) >= 2
    by_id = _by_id(events)
    for e in first:
        assert e.event_type == "Startup" and set(e.attrs) == set(
            FIRST_CALL_ATTRS)
        parent = by_id[e.parent]
        assert parent.name == SERVING_SPANS.dispatch
        assert parent.attrs["k"] == e.attrs["k"]
        assert min(e.attrs[a] for a in ("trace_us", "lower_us",
                                        "backend_us")) > 0
        assert sum(e.attrs[a] for a in ("trace_us", "lower_us",
                                        "backend_us")) <= e.duration * 1e6
        assert e.attrs["fun"].startswith("jit(")
        assert e.attrs["cache"] in COMPILE_CACHE
        inside = [c for c in events if c.event_type == "Compile"
                  and e.start <= c.end <= e.end and c.tid == e.tid]
        assert {c.name for c in inside} == set(COMPILE_SPANS)
    assert not [c for c in events if c.attrs.get("recompile")]


def test_the_operator_sees_what_each_first_call_cost(mixed_engine):
    """Two prom series and one JSONL line a first call, from the span's
    own sums."""
    events, _, eng, log_path = mixed_engine
    first = [e for e in events if e.name == STARTUP_SPANS.program]
    text = eng._prom.render()
    for stage in ("trace", "lower", "backend"):
        line, = [ln for ln in text.splitlines() if ln.startswith(
            f'paddle_tpu_serving_compile_seconds_total{{stage="{stage}"}}')]
        assert float(line.split()[-1]) == pytest.approx(
            sum(e.attrs[stage + "_us"] for e in first) / 1e6)
    counted = [ln for ln in text.splitlines() if ln.startswith(
        "paddle_tpu_serving_compile_cache_total{")]
    assert sum(float(ln.split()[-1]) for ln in counted) >= len(first)
    assert all(ln.split('result="')[1].split('"')[0] in COMPILE_CACHE
               for ln in counted)
    with open(log_path) as f:
        lines = [ev for ev in map(json.loads, f)
                 if ev["event"] == "program_compiled"]
    assert [(ev["k"], ev["spec"], ev["cache"], ev["recompile"], ev["fun"])
            for ev in lines] == [
        (e.attrs["k"], e.attrs["spec"], e.attrs["cache"], 0, e.attrs["fun"])
        for e in first]
    assert all(ev["role"] == "serving" and ev["seconds"] > 0
               for ev in lines)


def test_a_known_variant_compiled_again_is_a_recompilation(mixed_engine,
                                                           tmp_path):
    """A variant that has run loses its compiled program (here: its
    function's own cache is cleared; in a deployment: a new input shape or
    dtype): the dispatch that compiles it again opens no first-call span,
    the backend event carries `recompile=1`, and the operator gets the
    series and a `program_compiled` line that says so."""
    _, _, eng, _ = mixed_engine
    log = obs.EventLog(str(tmp_path / "ev.jsonl"))
    before = obs.set_event_log(log)
    try:
        for fn in eng._unified_cache.values():
            fn.clear_cache()
        eng.add_request(np.arange(4) % 64, max_new_tokens=1)
        with obs.capture_spans() as cap:
            while eng.has_work():
                eng.step()
    finally:
        obs.set_event_log(before)
    assert not [e for e in cap.events if e.name == STARTUP_SPANS.program]
    again = [e for e in cap.events if e.attrs.get("recompile")]
    assert again and {e.name for e in again} == {COMPILE_SPANS.backend}
    assert not obs.startup.RECOMPILES     # the engine took what was its own
    with open(log.path) as f:
        lines = [ev for ev in map(json.loads, f)
                 if ev["event"] == "program_compiled"]
    assert len(lines) == len(again)
    assert all(ev["recompile"] == 1 and ev["seconds"] > 0 for ev in lines)
    assert obs.startup_record()["recompiles"] >= len(again)


def test_nothing_is_recorded_of_a_request_while_the_collector_is_off():
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, chunk=8)
    rid = eng.add_request(np.arange(5) % 64, max_new_tokens=2)
    collector.clear()
    assert not collector.enabled
    out = eng.run(max_steps=10)
    assert len(out[rid]) == 2
    assert collector.drain() == []


def test_starved_rows_and_granted_tokens_of_a_hand_built_step():
    """`n_starved` / `pre_tokens` / `budget` say what the token budget did
    to the resident prefilling rows: three rows share 12 packed tokens a
    step (4 slots + one chunk of 8), a decode row takes 1 first."""
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=4, block_size=16,
                        num_blocks=16, chunk=8, decode_burst=1)
    eng.add_request(np.arange(5) % 64, max_new_tokens=12)    # row A
    eng.add_request(np.arange(20) % 64, max_new_tokens=4)    # row B
    eng.add_request(np.arange(20) % 64, max_new_tokens=4)    # row C
    seen = []
    for _ in range(4):
        with obs.capture_spans() as cap:
            eng.step()
        seen += [tuple(e.attrs[k] for k in ("n_dec", "n_pre", "n_starved",
                                            "pre_tokens", "budget"))
                 for e in cap.events if e.name == SERVING_SPANS.dispatch]
    # step 1: A's 5 and 7 of B's 8 fill the budget, C rides with nothing;
    # step 2: A decodes (1), B gets a whole chunk, C the 3 left;
    # step 3: B's last 5, C 6 of the 11 left; step 4: B decodes too, C 8
    assert seen == [(0, 3, 1, 12, 12), (1, 2, 0, 11, 12), (1, 2, 0, 11, 12),
                    (2, 1, 0, 8, 12)]
    c = eng.slots[2]
    assert (c.starved_steps, c.prefill_steps) == (1, 3)


def _blocked_engine(why):
    """An engine whose queue's head waits for the named reason."""
    cfg = tiny_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, block_size=8, num_blocks=16, chunk=8)
    long = np.arange(16) % 64
    if why == "none":
        eng = ServingEngine(params, cfg, **kw)
        eng.add_request(long, max_new_tokens=8)
    elif why == "slot":
        eng = ServingEngine(params, cfg, **kw)
        for _ in range(3):
            eng.add_request(long, max_new_tokens=8)
    elif why == "pages":            # 5 usable pages, 3 a request
        eng = ServingEngine(params, cfg, **dict(kw, num_blocks=6))
        for _ in range(2):
            eng.add_request(long, max_new_tokens=8)
    elif why == "prefix":           # the twin waits for its owner's pages
        eng = ServingEngine(params, cfg, prefix_share=True, **kw)
        for _ in range(2):
            eng.add_request(long, max_new_tokens=8)
    else:
        eng = ServingEngine(params, cfg, **kw)
        for _ in range(3):
            eng.add_request(long, max_new_tokens=8)
        eng.step()
        eng.drain()
    return eng


@pytest.mark.parametrize("why", ADMIT_BLOCKED._fields)
def test_the_admission_span_says_why_the_head_waits(why):
    eng = _blocked_engine(why)
    with obs.capture_spans() as cap:
        eng.step()
    (adm,) = [e.attrs for e in cap.events
              if e.name == SERVING_SPANS.admission]
    assert tuple(adm) == ADMISSION_ATTRS
    assert adm["blocked"] == getattr(ADMIT_BLOCKED, why)
    assert adm["queue"] == len(eng.queue) == (0 if why == "none" else 1)
    assert adm["admitted"] == (0 if why == "draining" else
                               2 if why == "slot" else 1)
    assert adm["preempted"] == 0


def test_a_fetch_carries_the_router_counts_of_the_step_it_landed():
    """Two steps with different routing: each `serving_fetch` closes with
    the counts of the step IT landed, not of the one before."""
    cfg = tiny_pattern_cfg()
    params = QN.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, chunk=8, decode_burst=1)
    eng.add_request(np.arange(8) % 64, max_new_tokens=3)
    landed = []
    land = eng._land

    def spy(f):     # the step's own stats, read before the engine does
        stats = np.asarray(jax.device_get(f.out[-1]))
        landed.append((int(stats[..., 0].sum()), int(stats[..., 1].sum()),
                       int(stats[..., 2].max()), int(stats[..., 3].sum()),
                       int(stats[..., 4].max())))
        return land(f)
    eng._land = spy
    with obs.capture_spans() as cap:
        eng.run()
    fetch = [tuple(e.attrs[k] for k in MOE_FETCH_ATTRS)
             for e in cap.events if e.name == SERVING_SPANS.fetch]
    assert len(fetch) >= 2 and fetch == landed
    assert fetch[0] != fetch[1]     # 8 prompt tokens, then 1 decode token


def _calls(tree, attr):
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute) and n.func.attr == attr]


def _from_tuple(node, tuple_name, fields):
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == tuple_name and node.attr in fields)


def test_every_pallas_call_has_a_name_in_KERNELS():
    used = set()
    files = glob.glob(os.path.join(REPO, "paddle_tpu", "kernels", "pallas",
                                   "*.py"))
    for path in files:
        for call in _calls(ast.parse(open(path).read()), "pallas_call"):
            name = {k.arg: k.value for k in call.keywords}.get("name")
            assert _from_tuple(name, "KERNELS", KERNELS._fields), \
                f"{path}:{call.lineno}: pallas_call without name=KERNELS.x"
            used.add(name.attr)
    assert used == set(KERNELS._fields)
    assert len(set(KERNELS)) == len(KERNELS)


def test_no_scope_or_serving_span_is_a_free_string():
    for path in glob.glob(os.path.join(REPO, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        tree = ast.parse(open(path).read())
        for call in _calls(tree, "named_scope"):
            assert _from_tuple(call.args[0], "SCOPES", SCOPES._fields), \
                f"{path}:{call.lineno}"
    serving = ast.parse(open(os.path.join(
        REPO, "paddle_tpu", "inference", "serving.py")).read())
    spans = [n for n in ast.walk(serving) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Name) and n.func.id == "RecordEvent"]
    assert len(spans) >= len(SERVING_SPANS) + len(REQUEST_SPANS)
    for call in spans:
        assert any(_from_tuple(call.args[0], name, tup._fields)
                   for name, tup in (("SERVING_SPANS", SERVING_SPANS),
                                     ("REQUEST_SPANS", REQUEST_SPANS),
                                     ("STARTUP_SPANS", STARTUP_SPANS))), \
            call.lineno
    # the start-up record's names: its module takes them from the tuples too
    startup = ast.parse(open(os.path.join(
        REPO, "paddle_tpu", "observability", "startup.py")).read())
    free = [n.value for n in ast.walk(startup) if isinstance(n, ast.Constant)
            and isinstance(n.value, str)
            and n.value in set(STARTUP_SPANS) | set(COMPILE_SPANS)]
    assert free == []


# -- the benchmark's readers ------------------------------------------------
# Which per-layer entries exist, how many, what they are called and which
# cells list them is BENCHMARK.json's alone. This section knows the RULES an
# entry is held to and finds the entries as the harness does, through
# `harness.metrics_of`. A slice recorded on the chip states its cell and
# keys what it recorded by what was READ (`_signature`), so an entry that is
# renamed, merged or shared by more cells finds its value again.
SLICE_FILES = sorted(map(os.path.basename, glob.glob(
    os.path.join(DATA, "ptrace-*.json.gz"))))
# what each span may carry, by the tuple the call site takes it from
SPAN_ATTRS = {SERVING_SPANS.dispatch: (DISPATCH_ATTRS + CHUNK_DISPATCH_ATTRS
                                       + SSM_DISPATCH_ATTRS
                                       + LATENT_DISPATCH_ATTRS
                                       + WINDOW_DISPATCH_ATTRS),
              SERVING_SPANS.fetch: MOE_FETCH_ATTRS + MOE_LOCAL_FETCH_ATTRS,
              SERVING_SPANS.admission: ADMISSION_ATTRS,
              REQUEST_SPANS.first_token: FIRST_TOKEN_ATTRS,
              REQUEST_SPANS.end: REQUEST_END_ATTRS}
# PR 38 brought the request spans, and these attributes of older spans
PR38_ATTRS = {"n_starved", "pre_tokens", "budget"} | set(ADMISSION_ATTRS)


@functools.lru_cache(maxsize=None)
def _slice(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)     # shared: nobody writes to it


def _meta(name):
    return harness.load_json("metrics", name + ".json")


def _signature(name):
    """What an entry reads, whatever it is called."""
    meta = _meta(name)
    return json.dumps([meta["reader"], meta.get("params", {})],
                      sort_keys=True)


def _form(name):
    """The form of the trace an entry's reader reads, by what the reader's
    module imports: "program_trace" (the program's own names), else
    "trace_reduce" (the reduced form alone), else None (no trace)."""
    reader = importlib.import_module(
        f"chipbench.readers.{_meta(name)['reader']}")
    return next((f for f in ("program_trace", "trace_reduce")
                 if hasattr(reader, f)), None)


def _named(params):
    """The spans and the span attributes a metric file names."""
    spans = [params.get(k, []) for k in ("spans", "span", "until", "per")]
    return ({s for v in spans for s in ([v] if isinstance(v, str) else v)},
            {params[k] for k in ("attr", "num", "den") if params.get(k)}
            | set(params.get("attrs", [])) | set(params.get("per_unit", {})))


def traced(spec, cell):
    """The entries a cell reports whose reader reads a trace."""
    return [m for m in harness.metrics_of(spec, cell, "per_layer")
            if _form(m["name"])]


def slices_of(cell):
    """The slices recorded for a cell: none, one or several."""
    return [s for s in SLICE_FILES if _slice(s)["note"]["cell"] == cell]


def cases_of(spec):
    """(entry, cell, slice): every traced entry of every cell on every
    slice of that cell. An entry without a list, one that three cells
    share, a suffix nobody has seen and a cell without a slice (it has no
    cases) are all legal."""
    return [(m, cell["name"], s) for cell in spec["workloads"]
            for s in slices_of(cell["name"]) for m in traced(spec, cell)]


def _id(v):
    return v["name"] if isinstance(v, dict) else v.split(".json")[0]


SPEC = harness.load_spec()
CASES = cases_of(SPEC)


@functools.lru_cache(maxsize=None)
def _run(slice_name, names="all"):
    """A run as the readers see it: the slice as the program trace, and
    its reduced form as the harness's own. With `names` "none" it is the
    run of a program that names nothing: the benchmark's own spans, paths
    without scopes. With "pr37" it is the run of PR 38's parent: no
    request span, and no older span has the attributes PR 38 gave it."""
    pt = _slice(slice_name)
    if names == "none":
        pt = {"devices": {k: [e[:3] + [re.sub(r"[^/()]+", "x", e[3])]
                              for e in v] for k, v in pt["devices"].items()},
              "async": {k: [e[:3] + [""] for e in v]
                        for k, v in pt["async"].items()},
              "host": [h for h in pt["host"] if h[0] in harness.HOST_SPANS]}
    elif names == "pr37":
        pt = dict(pt, host=[
            h[:3] + [{k: v for k, v in h[3].items() if k not in PR38_ATTRS}]
            for h in pt["host"] if h[0] not in set(REQUEST_SPANS)])
    reduced = {"host": [h[:3] for h in pt["host"]
                        if h[0] in harness.HOST_SPANS]}
    for key in ("devices", "async"):
        reduced[key] = {k: [e[:3] for e in v] for k, v in pt[key].items()}
    return {"program_trace": pt, "trace": reduced, "facts": {}, "devices": [
        types.SimpleNamespace(device_kind="TPU v5 lite")]}


# The rules. The rehearsal at the end of the section asks them again of
# tables that are not the benchmark's, by calling these same functions.
@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]
                                  if _form(m["name"]) == "program_trace"])
def test_metric_files_name_only_what_the_program_names(name):
    """A rename in the program fails here instead of reading 0 there."""
    params = _meta(name).get("params", {})
    spans, attrs = _named(params)
    assert spans <= set(SERVING_SPANS) | set(REQUEST_SPANS)
    for key in ("scopes", "of"):
        assert set(params.get(key, [])) <= set(SCOPES), name
    if "kernel" in params:
        assert params["kernel"] in KERNELS
    assert attrs <= set(SPAN_ATTRS.get(params.get("span"), ()))
    if params.get("num") == "blocked":
        assert params["num_equals"] in tuple(ADMIT_BLOCKED)


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]
                                  if _meta(m["name"])["reader"]
                                  == "startup_record"])
def test_metric_files_name_only_keys_of_the_startup_record(name):
    """As the scope and span names are held: a key the record drops or
    renames fails here instead of reading nothing there."""
    record = obs.startup_record()
    assert isinstance(record[_meta(name)["params"]["key"]], float)
    assert set(_meta(name)["params"]) == {"key"}


@pytest.mark.parametrize("entry,cell,slice_name", CASES, ids=_id)
def test_reader_on_a_slice_recorded_on_the_chip(entry, cell, slice_name):
    """Where the slice recorded what this entry reads, the value the chip
    read; in range whatever it reads (None where the slice holds nothing
    of it: a slice of host spans has no device operations)."""
    value = harness.read_metric(entry["name"], _run(slice_name))
    recorded = _slice(slice_name)["note"]["expected"].get(
        _signature(entry["name"]))
    if recorded is not None:
        assert value == pytest.approx(recorded, rel=1e-6)
    if value is not None:
        assert 0.0 <= value and (entry["unit"] != "%" or value <= 100.0)


@pytest.mark.parametrize("entry,cell,slice_name", CASES, ids=_id)
def test_reader_returns_nothing_without_the_programs_names(entry, cell,
                                                           slice_name):
    """Two parents. One names nothing: a reader of the program's names
    reads None there, a reader of the reduced form alone (the exposed
    collectives) what it reads anyway. The other is PR 38's: an entry that
    names a request span or an attribute PR 38 brought reads None there,
    any other (the slot fill, whose attributes PR 29 brought) what it
    reads anyway."""
    name = entry["name"]
    spans, attrs = _named(_meta(name).get("params", {}))
    whole = harness.read_metric(name, _run(slice_name))
    assert harness.read_metric(name, _run(slice_name, "none")) == (
        None if _form(name) == "program_trace" else whole)
    assert harness.read_metric(name, _run(slice_name, "pr37")) == (
        None if spans & set(REQUEST_SPANS) or attrs & PR38_ATTRS else whole)


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_the_share_metrics_of_a_cell_divide_its_program(cell, spec=SPEC):
    """A cell's shares have one `of` and name disjoint scopes of it, one
    of them none (the unscoped share); what they leave of `of` is named by
    a share of another cell (the gradient sync's scopes, which the hybrid
    cell alone runs). On a slice with device operations they sum to 100."""
    def shares(entries):
        metas = [_meta(m["name"]) for m in entries]
        return [m for m in metas if m["reader"] == "scope_share"]
    mine = shares(traced(spec, harness.cell_of(spec, cell)))
    if not mine:
        return
    of = mine[0]["params"]["of"]
    named = [s for m in mine for s in m["params"]["scopes"]]
    assert all(m["params"]["of"] == of for m in mine)
    assert len(set(named)) == len(named)
    assert sum(1 for m in mine if not m["params"]["scopes"]) == 1
    assert set(named) <= set(of) <= {
        s for m in shares(spec["per_layer"]) if m["params"]["of"] == of
        for s in m["params"]["scopes"]}
    for s in slices_of(cell):
        if _slice(s)["devices"]:
            assert sum(harness.read_metric(m["name"], _run(s))
                       for m in mine) == pytest.approx(100.0, abs=1.0), s


def test_what_no_slice_has_recorded_yet():
    """Fails on nothing: it prints (`-s`) what a later PR could record. An
    entry without a recording is still read and range-checked above."""
    for cell in SPEC["workloads"]:
        slices = slices_of(cell["name"])
        have = {sig for s in slices for sig in _slice(s)["note"]["expected"]}
        print(f"{cell['name']}: {len(slices)} slices, no recording of",
              [m["name"] for m in traced(SPEC, cell)
               if _signature(m["name"]) not in have])


def test_the_table_fits_its_cap_and_cells_and_trace_readers_meet():
    """All that is pinned of the table."""
    n = len(SPEC["per_layer"])
    assert n <= 128, f"per_layer holds {n} of 128 entries: {128 - n} free"
    reported = [{m["name"] for m in traced(SPEC, c)}
                for c in SPEC["workloads"]]
    assert all(reported)                    # every cell reads a trace
    assert set().union(*reported) == {      # and every such entry has a cell
        m["name"] for m in SPEC["per_layer"] if _form(m["name"])}


@pytest.mark.parametrize("slice_name", SLICE_FILES, ids=_id)
def test_a_slice_keys_what_it_recorded_by_what_was_read(slice_name):
    """In `_signature`'s form, never by an entry's name: `recorded_as`
    keeps those for a reader of the file, and nobody looks them up."""
    note = _slice(slice_name)["note"]
    assert isinstance(note["cell"], str) and note["expected"]
    for sig in note["expected"]:
        reader, params = json.loads(sig)
        assert sig == json.dumps([reader, params], sort_keys=True)


@pytest.mark.parametrize("slice_name", [
    s for s in SLICE_FILES
    if any(h[0] == REQUEST_SPANS.first_token for h in _slice(s)["host"])],
    ids=_id)
def test_a_recorded_first_token_is_four_parts_and_one_landing_step(
        slice_name):
    """On the chip as on the CPU: every first token of the slice has four
    non-negative parts, was handed over one engine step after the
    dispatch that sampled it (or in the same call, by a tail settle), and
    the medians of the parts add up to about the median of their sum."""
    pt = _slice(slice_name)
    ft = [h[3] for h in pt["host"] if h[0] == REQUEST_SPANS.first_token]
    assert len(ft) >= 5 and all(tuple(a) == FIRST_TOKEN_ATTRS for a in ft)
    parts = ("queue_us", "wait_us", "prefill_us", "land_us")
    assert all(a[k] >= 0 for a in ft for k in parts)
    assert {a["land_steps"] for a in ft} <= {0, 1}
    assert all(a["prefill_steps"] >= -(-a["prompt_len"] // 128) for a in ft)
    walks = [(h[1], h[1] + h[2]) for h in pt["host"]
             if h[0] == SERVING_SPANS.walk]
    for h in pt["host"]:
        if h[0] in set(REQUEST_SPANS):      # instants inside a walk
            assert any(a <= h[1] and h[1] + h[2] <= b for a, b in walks)
    ends = [h[3] for h in pt["host"] if h[0] == REQUEST_SPANS.end]
    assert ends and all(tuple(a) == REQUEST_END_ATTRS for a in ends)
    assert all(a["status"] == "ok" and a["out_tokens"] >= 1 for a in ends)


# The rehearsal: the rules on the tables the next two PRs bring. PR 41's (a
# `benchmark` PR: entries out, what reads the same for all the cells that
# report what it moves merged into one entry without a list, a list
# dropped) and a `model_config` PR's (an entry with a suffix and a metric
# file nobody has seen). Each edit picks its entries by what they read, so
# on the table those PRs leave it still finds some, or nothing to do.
def _put(spec, entry, meta):
    with open(os.path.join(harness.HERE, "metrics",
                           entry["name"] + ".json"), "w") as f:
        json.dump(dict(meta, name=entry["name"]), f)
    spec["per_layer"].append(entry)


def _out(spec, names):
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] not in names]
    return names


def _two_are_taken_out(spec):
    return _out(spec, [m["name"] for c in spec["workloads"]
                       for m in traced(spec, c)
                       if _meta(m["name"])["reader"] != "scope_share"][:2])


def _what_reads_the_same_becomes_one_listless_entry(spec):
    groups = {}
    for m in spec["per_layer"]:
        if "workloads" in m and _form(m["name"]):
            groups.setdefault((_signature(m["name"]), m["moves"]),
                              []).append(m)
    for (_, moves), ms in groups.items():
        cells = {c["name"] for c in spec["workloads"] if moves in {
            e["name"] for e in harness.metrics_of(spec, c, "end_to_end")}}
        if len(ms) > 1 and {w for m in ms for w in m["workloads"]} == cells:
            _out(spec, [m["name"] for m in ms])
            entry = dict(ms[0], name=ms[0]["name"].split(".")[0] + ".merged")
            del entry["workloads"]
            _put(spec, entry, _meta(ms[0]["name"]))


def _a_span_attr_entry_loses_its_list(spec):
    next((m for m in spec["per_layer"] if "workloads" in m
          and _meta(m["name"])["reader"] == "span_attr"),
         {}).pop("workloads", None)


def _a_new_suffix_and_file_are_added(spec):
    cell = next(c for c in spec["workloads"] if slices_of(c["name"]))
    shared = {"unit": "tokens", "layer": "serving engine", "moves":
              harness.metrics_of(spec, cell, "end_to_end")[0]["name"]}
    _put(spec, dict(shared, name="first_prompt_len_mean.mla", better="lower",
                    source="program_span", workloads=[cell["name"]]),
         dict(shared, reader="span_attr", params={
             "span": REQUEST_SPANS.first_token, "attrs": ["prompt_len"],
             "stat": "mean"}))


EDITS = [_two_are_taken_out, _what_reads_the_same_becomes_one_listless_entry,
         _a_span_attr_entry_loses_its_list, _a_new_suffix_and_file_are_added]


@pytest.mark.parametrize("edits", [[e] for e in EDITS] + [EDITS], ids=[
    e.__name__[1:] for e in EDITS] + ["all_four"])
def test_the_rules_hold_on_the_tables_the_next_prs_bring(edits, tmp_path,
                                                         monkeypatch):
    """On a copy (the table as `load_spec` hands it out anew, the metric
    files in `tmp_path` with `harness.HERE` pointed there; nothing of the
    benchmark is edited): the cases are built without error, every new
    entry has one, every rule holds of every case, and whatever a slice
    recorded that was found before is found again, under whatever name,
    but for what was taken out."""
    shutil.copytree(os.path.join(harness.HERE, "metrics"),
                    tmp_path / "metrics")
    monkeypatch.setattr(harness, "HERE", str(tmp_path))

    def found(spec):
        return {(cell, s, _signature(m["name"])): m["name"]
                for m, cell, s in cases_of(spec) if _signature(m["name"])
                in _slice(s)["note"]["expected"]}
    spec = harness.load_spec()
    before, old = found(spec), {m["name"] for m in spec["per_layer"]}
    gone = [n for edit in edits for n in edit(spec) or []]
    cases = cases_of(spec)
    assert {m["name"] for m in spec["per_layer"]} - old <= {
        m["name"] for m, _, _ in cases}
    for m, cell, s in cases:
        if _form(m["name"]) == "program_trace":
            test_metric_files_name_only_what_the_program_names(m["name"])
        test_reader_on_a_slice_recorded_on_the_chip(m, cell, s)
        test_reader_returns_nothing_without_the_programs_names(m, cell, s)
    for cell in spec["workloads"]:
        test_the_share_metrics_of_a_cell_divide_its_program(cell["name"],
                                                            spec)
    assert set(found(spec)) >= {k for k, name in before.items()
                                if name not in gone}


def _hand_trace():
    """Five spans of one name inside a window, one outside it, one
    without the attributes."""
    host = [["traced_window", 100, 1000, {}]]
    for i, (a, b) in enumerate([(1, 10), (2, 20), (3, 30), (4, 40),
                                (5, 50)]):
        host.append(["s", 200 + 100 * i, 10, {"a": a, "b": b, "c": i % 2}])
    host.append(["s", 50, 10, {"a": 999, "b": 999, "c": 1}])    # too early
    host.append(["s", 900, 10, {"other": 1}])
    return {"program_trace": {"devices": {}, "async": {}, "host": host}}


@pytest.mark.parametrize("params,expected", [
    (dict(attrs=["a"], stat="p50"), 3.0),
    (dict(attrs=["a"], stat="p95"), 4.8),
    (dict(attrs=["a", "b"], stat="p50", scale=0.001), 0.033),
    (dict(attrs=["b"], stat="mean"), 30.0),
    (dict(attrs=["a"], stat="sum", scale=2.0), 30.0),
    (dict(attrs=["missing"], stat="p50"), None),
])
def test_span_attr_reads_a_statistic_of_attributes_added(params, expected):
    from chipbench.readers import span_attr
    assert span_attr.read(_hand_trace(), "s", **params) == \
        (None if expected is None else pytest.approx(expected))
    assert span_attr.read(_hand_trace(), "no_such_span", **params) is None


@pytest.mark.parametrize("params,expected", [
    (dict(num="a", den="b"), 10.0),                     # 15 / 150
    (dict(num="a", den="b", den_times=0.5), 20.0),
    (dict(num="c", num_equals=1), 40.0),                # 2 of 5 spans
    (dict(num="a"), 300.0),                             # 15 / 5 spans
    (dict(num="a", den="missing"), None),
    (dict(num="missing"), None),
])
def test_span_attr_ratio_reads_sums_and_counts(params, expected):
    from chipbench.readers import span_attr_ratio
    assert span_attr_ratio.read(_hand_trace(), "s", **params) == \
        (None if expected is None else pytest.approx(expected))


def test_scope_of_reads_paths_as_the_profiler_writes_them():
    s = set(SCOPES)
    f = program_trace.scope_of
    assert f("jit(step)/jvp()/while/body/closed_call/attn/qkv/dot_general:",
             s) == "qkv"
    assert f("jit(step)/transpose(jvp(head_loss))/add_any:", s) == \
        "head_loss"
    assert f("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/attn/flash/flash_fwd/pallas_call:",
             s) == "flash"
    assert f("jit(step)/jvp()/while/body/dynamic_update_slice:", s) is None
    assert f("", s) is None
    assert f("jit(fn)/burst/while/body/qkv/dot_general:",
             {"burst"}) == "burst"
    assert program_trace.kernel_of(
        "ragged_paged_attn.7|tpu_custom_call|bf16[64,16,8,128]") == \
        "ragged_paged_attn"
    assert program_trace.kernel_of("fusion.3|fusion|bf16[8]") is None
