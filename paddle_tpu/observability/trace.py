"""Chrome-trace spans, unified with the profiler's scheduler machinery.

``span(name)`` IS the profiler's :class:`RecordEvent` — a span opened
through the observability surface lands in the same process-global
collector the :class:`paddle_tpu.profiler.Profiler` state machine drains,
so its summary tables and ``export_chrome_tracing`` windows see telemetry
spans with no extra plumbing. ``write_chrome_trace`` is the standalone
export for code that wants a trace file without driving a Profiler
session (same JSON schema as the profiler's exporter, so the files are
interchangeable in chrome://tracing / Perfetto).

This module also owns the NAMES the program gives its work, so that a
trace of one commit can be held against a trace of the next:

* ``SERVING_SPANS`` — the host spans of one ``ServingEngine.step()``;
* ``DISPATCH_ATTRS`` — the attributes of the ``serving_unified_dispatch``
  span (stats of the event in a profiler trace), and ``SSM_DISPATCH_ATTRS``
  — the ones a model with a recurrent state adds, ``WINDOW_DISPATCH_ATTRS``
  — a model with windowed layers'; ``MOE_FETCH_ATTRS`` —
  the attributes a model with routed experts puts on ``serving_fetch``;
  ``ADMISSION_ATTRS`` — what ``serving_admission`` closes with, and
  ``ADMIT_BLOCKED``, the values of its ``blocked``;
* ``REQUEST_SPANS`` — the two instant spans of a request's life inside
  ``serving_walk``, with ``FIRST_TOKEN_ATTRS`` and ``REQUEST_END_ATTRS``;
  ``REQUEST_PHASES`` — the four back-dated collector events that tile a
  request's way from submission to its first token;
* ``STARTUP_SPANS`` — the three live spans of the program's own start-up
  work (``event_type="Startup"``), and ``FIRST_CALL_ATTRS``, what a
  ``startup_program_first_call`` carries; ``COMPILE_SPANS`` — the three
  back-dated events jax's own compile events become
  (``event_type="Compile"``), with ``COMPILE_ATTRS`` and ``COMPILE_CACHE``,
  the values of their ``cache`` (:mod:`.startup` makes and reads them);
* ``SCOPES`` — the ``jax.named_scope``s inside the compiled programs (the
  serving step, the dense train step, the hybrid train step). A device
  operation's ``op_name`` path carries them; an operation under none is
  work no line of the program asked for by name;
* ``KERNELS`` — the ``name=`` of every ``pallas_call``; the compiler names
  the custom call after it (``ragged_paged_attn.7``).

Call sites take the names from these tuples (``SCOPES.qkv``), never from a
string of their own; the benchmark's metric files name the same strings
and a test holds the two together.
"""

from __future__ import annotations

import collections
import json
import os
from typing import Iterable, Optional

from ..profiler.utils import HostEvent, RecordEvent, collector

__all__ = ["span", "capture_spans", "write_chrome_trace", "SERVING_SPANS",
           "DISPATCH_ATTRS", "SSM_DISPATCH_ATTRS", "LATENT_DISPATCH_ATTRS",
           "WINDOW_DISPATCH_ATTRS", "MOE_FETCH_ATTRS",
           "MOE_LOCAL_FETCH_ATTRS",
           "ADMISSION_ATTRS", "ADMIT_BLOCKED", "REQUEST_SPANS",
           "REQUEST_PHASES", "FIRST_TOKEN_ATTRS", "REQUEST_END_ATTRS",
           "STARTUP_SPANS", "FIRST_CALL_ATTRS", "COMPILE_SPANS",
           "COMPILE_ATTRS", "COMPILE_CACHE", "SCOPES", "KERNELS"]

span = RecordEvent


def _names(typename, **names):
    """A tuple of names that also answers by attribute."""
    return collections.namedtuple(typename, names)(**names)


# One engine step: `step` is the whole call, the others are its children
# and cover it without holes. With one step in flight the call dispatches
# step n+1 (pack, upload, dispatch) and THEN fetches and walks step n.
SERVING_SPANS = _names(
    "ServingSpans",
    step="serving_step",            # the whole ServingEngine.step()
    sweep="serving_sweep",          # notifications, deadlines, overload
    admission="serving_admission",  # _admit() and the pool's peak
    pack="serving_pack",            # the packed host arrays, _pick_burst
    upload="serving_upload",        # key split + jnp.asarray of each array
    dispatch="serving_unified_dispatch",   # the call of the one program
    fetch="serving_fetch",          # the host blocked on the device
    walk="serving_walk",            # lens, pages, acceptance, token walk
    metrics="serving_metrics")      # _step_metrics, _numerics_kv_poll

# Attributes of the dispatch span: the engine step's number, the burst
# size, decode and prefill rows, packed query tokens, KV positions
# attended (summed over the rows that run and over the step's k passes),
# and the (row, page) pairs those positions fill: what the attention
# kernel walks, of k x max_batch x max_blocks_per_seq table slots;
# `kv_tiles`, the (page, tile) pairs the in-place append writes over the k
# passes (`kernels.pallas.kv_append`: its trip count, summed); and
# `in_flight`, 1 when the step before had not been fetched at this
# dispatch (the device goes from one to the next without the host). What
# the token budget did to the prefilling rows: `n_starved` of the `n_pre`
# resident prefilling rows rode the step with no grant, the others shared
# `pre_tokens` prompt tokens of a `budget` of packed tokens a step.
DISPATCH_ATTRS = ("step", "k", "n_dec", "n_pre", "q_tokens", "kv_tokens",
                  "attn_pages", "kv_tiles", "in_flight", "n_starved",
                  "pre_tokens", "budget")
# A model with a recurrent state adds: rows whose state the first pass read
# and wrote (the chunk scan's), rows of the k - 1 burst passes (the state
# update's, summed), and tokens through its mixer over all k passes.
SSM_DISPATCH_ATTRS = ("ssm_scan_rows", "ssm_update_rows", "ssm_tokens")
# A model with routed experts learns what a step's router chose only from
# the step's fetch, so these ride the `serving_fetch` span that LANDED the
# step (set before it closes): held experts whose weights the step read,
# summed over its layers and passes; token-expert assignments to held
# experts; the largest number of assignments one held expert got in a
# layer of a pass; the real tiles the grouped expert product walked, summed
# alike; and the rows of a tile (the tallest of the step's passes: the
# height follows the pass, `kernels.pallas.moe.tile_rows`). Rows a tile =
# assignments / tiles, and that over the height is the tiles' fill.
MOE_FETCH_ATTRS = ("moe_experts_touched", "moe_assignments", "moe_load_max",
                   "moe_tiles", "moe_tile_rows")
# A router that keeps a token to a few GROUPS of experts (a group lies on
# one chip) adds: tokens with at least one pick among the held experts,
# and the tokens routed (padding is not), both summed over the step's
# expert layers and passes.
MOE_LOCAL_FETCH_ATTRS = ("moe_local_tokens", "moe_tokens")
# A model whose cache is a LATENT (one vector a token and layer, shared by
# all heads; `kv_tokens`, `attn_pages` and `kv_tiles` above count its pages
# alike) adds: prompt tokens of the rows admitted in this step that prefix
# sharing found computed (never run again), and the (query, key) pairs its
# attention computes beyond the one a row that `kv_tokens` counts: a row of
# q tokens from position p adds q p + q (q + 1) / 2 - (p + q), so
# `kv_tokens + chunk_ctx_tokens` is the causal pair count of the step; and
# `attn_shared_pages`: of the step's `attn_pages`, the (row, page) pairs
# that rows of one token whose tables share leading pages attended
# TOGETHER (phase A of a group of two or more rows in
# `kernels.pallas.mla_attention`), summed over the k passes.
LATENT_DISPATCH_ATTRS = ("prefix_hit_tokens", "chunk_ctx_tokens",
                         "attn_shared_pages")
# A model with WINDOWED attention layers (two page lifetimes) adds:
# `kv_layer_tokens`, the positions the step's attention kernels attend
# summed over the LAYERS too (`kv_tokens` counts a row's whole context once,
# as a layer that attends everything reads it; a window layer reads
# min(kv_len, window - 1 + q_len) of a row a pass), `win_attn_pages`, the
# (row, page) pairs a window layer's kernel walks over the k passes, and
# `win_pages_freed`, the window-lifetime pages this pack gave back behind
# the rows' windows.
WINDOW_DISPATCH_ATTRS = ("kv_layer_tokens", "win_attn_pages",
                         "win_pages_freed")
# A model whose attention is `ragged_paged_attn` adds what the kernel's WIDE
# arm walks (`kernels.pallas.ragged_paged_attention.wide_arm_pages`, the
# kernel's own rules on the host): `chunk_pages`, the (row, page) pairs of
# the rows whose folded queries pass one sublane tile (the prefill chunks),
# summed over the passes and over the LAYERS as `kv_layer_tokens` is, a
# window layer counting the pages its window lets the row read; and
# `chunk_masked_pages`, those of them in a block of pages that takes the
# masked soft-max update (an edge crosses it). Their ratio is how often the
# unmasked update runs.
CHUNK_DISPATCH_ATTRS = ("chunk_pages", "chunk_masked_pages")
# What `serving_admission` closes with: requests admitted by this call,
# the queue's depth after it, why the queue's head still waits (one of
# ADMIT_BLOCKED), and decode victims this call evicted for it.
ADMISSION_ATTRS = ("admitted", "queue", "blocked", "preempted")
ADMIT_BLOCKED = _names(
    "AdmitBlocked",
    none=0,         # the queue is empty: nobody waits
    slot=1,         # no free slot
    pages=2,        # the pool has too few free pages for the head
    prefix=3,       # an identical prefix is being prefilled by its owner
    draining=4)     # the engine admits nothing any more

# A request's way to its first token, from ONE record (`Request`'s marks):
# four phases that tile [submission, first token] without holes. The engine
# adds them to the collector when the token is handed over, each with its
# own start and end and the request's `rid` (a chrome trace shows a request
# as four bars); a profiler session gets the same durations as the stats of
# the instant span below, which anchors the hand-over on the device's clock.
REQUEST_PHASES = _names(
    "RequestPhases",
    queue="request_queue",      # submitted -> a slot and its pages
    wait="request_wait",        # admitted -> the first prompt tokens granted
    prefill="request_prefill",  # first grant -> dispatch of the last chunk
    land="request_land")        # that dispatch -> the token handed over
# Two instant spans inside `serving_walk`, one each a request.
REQUEST_SPANS = _names(
    "RequestSpans",
    first_token="serving_first_token",  # the first token's hand-over
    end="serving_request_end")          # the walk that finished the request
# `*_us`: the four phases in whole microseconds (they sum to the request's
# TTFT to the microsecond); `prefill_steps` / `starved_steps`: engine steps
# it was granted prompt tokens / rode resident with none; `land_steps`:
# engine steps from the dispatch of its last chunk to the call that handed
# the token over (the pipeline's depth: 1 with a step in flight).
FIRST_TOKEN_ATTRS = ("rid", "prompt_len", "queue_us", "wait_us", "prefill_us",
                     "land_us", "prefill_steps", "starved_steps",
                     "land_steps", "preemptions")
REQUEST_END_ATTRS = ("rid", "status", "out_tokens", "decode_steps",
                     "total_us", "preemptions")

# What happens before the first useful step. Three live spans where the
# program itself does start-up work; `observability.startup` keeps them,
# and the compile events below, in a ring of their own whether or not a
# profiler session is on.
STARTUP_SPANS = _names(
    "StartupSpans",
    import_="startup_import",       # first to last line of paddle_tpu/__init__
    engine="startup_engine_build",  # ServingEngine.__init__
    program="startup_program_first_call")   # a _unified(K, spec)'s first call
# The first call's burst size and spec-verify flag and, at its close, the
# compile events that ended inside it: their seconds by stage in whole
# microseconds, and the `fun` and `cache` of the longest backend compile
# (the program's own).
FIRST_CALL_ATTRS = ("k", "spec", "trace_us", "lower_us", "backend_us", "fun",
                    "cache")
# jax's three compile durations, one back-dated event each (start = end -
# duration): a function traced to a jaxpr, a jaxpr lowered to a module, a
# module compiled by the backend or loaded from the persistent cache.
COMPILE_SPANS = _names(
    "CompileSpans",
    trace="compile_trace", lower="compile_lower", backend="compile_backend")
# `fun`: jax's name of the function; on `compile_backend`, `cache` (one of
# COMPILE_CACHE) and, on a hit, jax's two figures in whole microseconds:
# the read's time and the compile time the entry saved; `recompile`: 1 on
# a backend compile that fired inside the dispatch of a variant that had
# run before; `nested`: 1 on an event with another stage open around it on
# its thread (a kernel's body traced inside a lowering), whose seconds are
# that stage's.
COMPILE_ATTRS = ("fun", "cache", "retrieval_us", "saved_us", "recompile",
                 "nested")
COMPILE_CACHE = _names(
    "CompileCache",
    hit="hit",              # the persistent cache held the program
    compiled="compiled",    # the cache was asked and had it not
    off="off")              # the cache was never asked for this program

SCOPES = _names(
    "Scopes",
    # serving program (inference/serving.py, inference/ragged_step.py)
    embed="embed", qkv="qkv", kv_write="kv_write", ragged_attn="ragged_attn",
    proj_mlp="proj_mlp", head="head", sample="sample", cow="cow",
    burst="burst",
    # the ragged kernel under a window, over the second lifetime's pools
    # (a layer of kind "window", models/trinity_mini.py)
    window_attn="window_attn",
    # a hybrid block's recurrent mixer beside attention (models/falcon_h1.py)
    rope="rope", ssm_in="ssm_in", ssm_conv="ssm_conv", ssm_scan="ssm_scan",
    ssm_out="ssm_out",
    # a layer pattern of linear-attention and gated-attention layers with
    # routed experts in every layer (models/qwen3_next.py)
    moe_route="moe_route", moe_experts="moe_experts", moe_shared="moe_shared",
    gdn_in="gdn_in", gdn_conv="gdn_conv", gdn_scan="gdn_scan",
    gdn_out="gdn_out",
    # latent attention (models/deepseek_v2.py): the kernel over latent
    # pages, and every projection around it (down and up projections, both
    # norms, rotary, the two absorptions, the output projection)
    mla_attn="mla_attn", mla_proj="mla_proj",
    # train programs (models/gpt.py, optimizer/); embed and qkv as above
    attn="attn", flash="flash", attn_out="attn_out", mlp="mlp",
    head_loss="head_loss", optimizer="optimizer",
    # around every collective, the mesh axis it crosses
    coll_mp="coll_mp", coll_dp="coll_dp", coll_pp="coll_pp")

KERNELS = _names(
    "Kernels",
    flash_fwd="flash_fwd", flash_bwd_dkv="flash_bwd_dkv",
    flash_bwd_dq="flash_bwd_dq", ragged_paged_attn="ragged_paged_attn",
    kv_append="kv_append", paged_attn="paged_attn", fused_adam="fused_adam",
    layer_norm_fwd="layer_norm_fwd", layer_norm_bwd="layer_norm_bwd",
    rms_norm_fwd="rms_norm_fwd", rms_norm_bwd="rms_norm_bwd", rope="rope",
    rowwise="rowwise", row_reduce="row_reduce",
    prim_layer_norm_fwd="prim_layer_norm_fwd",
    prim_layer_norm_bwd="prim_layer_norm_bwd",
    # the recurrent-state path of a Mamba-2 mixer (kernels/pallas/ssm.py)
    ssm_conv="ssm_conv", ssm_chunk_scan="ssm_chunk_scan",
    ssm_state_update="ssm_state_update",
    # the gated delta rule's state path (kernels/pallas/gdn.py) and the
    # grouped expert product (kernels/pallas/moe.py)
    gdn_chunk_scan="gdn_chunk_scan", gdn_state_update="gdn_state_update",
    moe_grouped_ffn="moe_grouped_ffn",
    # absorbed attention over latent pages and the latent's append
    # (kernels/pallas/mla_attention.py, kernels/pallas/latent_append.py)
    mla_paged_attn="mla_paged_attn", latent_append="latent_append")


class capture_spans:
    """Enable the host-span collector for a scope and hand back the events
    recorded inside it (independent of any Profiler session; nested inside
    one, the profiler keeps collecting — events are split, not lost)."""

    def __enter__(self):
        self._was_enabled = collector.enabled
        collector.enabled = True
        self.events: list = []
        return self

    def __exit__(self, *exc):
        self.events = collector.drain()
        collector.enabled = self._was_enabled
        if self._was_enabled:
            # hand the drained events back to the outer profiler session
            for ev in self.events:
                collector.add(ev)
        return False


def write_chrome_trace(path: str, events: Iterable[HostEvent],
                       extra: Optional[Iterable[dict]] = None) -> str:
    """Write chrome://tracing JSON from HostEvents (plus optional raw
    trace dicts — e.g. instant events from a JSONL log)."""
    trace = [ev.chrome() for ev in events]
    trace.extend(extra or ())
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": trace}, f)
    return path
