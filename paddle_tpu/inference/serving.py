"""Serving scheduler: continuous batching over the paged KV cache.

Reference: the fused_multi_transformer + block MHA serving path
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
paddle/fluid/inference/api/analysis_predictor.h). The reference kernels
exist there but the *scheduler* lived outside the repo; here it is
first-class (VERDICT r2 #4):

* **Block pool + admit/evict** — sequences own block tables into one shared
  [L, H_kv, num_blocks, bs, D] pool; finishing frees blocks for queued
  requests (paged attention's memory win).
* **Continuous batching** — decode runs every engine step for ALL running
  sequences (one compiled program, fixed max_batch; idle slots write to the
  reserved scratch block 0); requests join as slots/blocks free instead of
  waiting for the whole batch.
* **Chunked prefill** — prompts are processed `chunk` tokens per engine
  step, interleaved with decode, so a long prompt never stalls running
  decodes (bounded per-step latency).
* **Streaming** — each sampled token fires the request's callback
  immediately (detokenize hook).

TPU shape discipline: the engine has ONE step, the single-dispatch ragged
one. Every step builds ONE packed ragged token batch (decode rows
q_len=1, prefill chunks q_len≤chunk sharing a fixed token budget) and
runs ONE compiled program — GEMMs batched over the real tokens, the
unified ragged-paged-attention kernel, in-program sampling, prefill KV
appended in-program, plus a K-1-step decode-burst scan
(inference/ragged_step.py): one dispatch and one host fetch a step.
Supports an int8 (or fp8-e4m3) KV pool — quantize-on-append per-page
scales, dequantize in-kernel — so a fixed HBM budget admits ~2x the
sequences (kv_cache_dtype / `kv_pool_bytes`), and an adaptive
prefill/decode mix driven by the queue-depth and TTFT series the
Prometheus registry already exports. All cache state is functional jax
arrays threaded through the program.

ONE STEP IN FLIGHT (ISSUE 31): a call of `step()` admits, packs, uploads
and dispatches step n+1 FIRST and only then fetches and walks step n, so
the device goes from one step to the next without waiting for the host
(spans, in order: sweep, admission, pack, upload, dispatch of n+1, fetch
and walk of n, metrics). What makes that possible: a decode row's next
input token never leaves the device (`unified_step`'s ``prev_tok`` /
``last_tok``; the host packs the sentinel -1 in its place), and what step
n+1 runs is decided by counts the host has — committed progress plus
where the step in flight leaves each row if no EOS falls
(`ServingEngine._advance`). An EOS is learnt one step late: the row rides
one more step (its tokens dropped, counted in
``overlap_wasted_rows_total``) and its slot and pages are released when
that step has been walked. `Request.output`, `prefill_done`, `snapshot()`,
`load_stats()` and `prom` show COMMITTED (walked) progress and never
touch the device. `settle()` fetches and walks the step in flight; the
engine calls it itself, by what it sees in its own input, wherever the
next decision needs that result — speculative drafts, a preemption, a
cancel or an expiry of a running row, a drain, nothing left to pack — and
any outside read of `slots`, `lens`, the pools, their scales, `ssm_state`
or `conv_tail` settles first (``overlap_settles_total{reason}``). One
loop; the barrier sits before the pack or after the dispatch.

The model behind the step is a seam (`serving_model`, ISSUE 28):
the GPT block's answers are `GPTServing`; a configuration of another
architecture brings its own (``cfg.serving_model``, today
`models.falcon_h1`: GQA attention with RoPE beside a Mamba-2 mixer in
every block). A model with a recurrent mixer gets a SECOND kind of
per-request device state beside the KV pages: one recurrent state and
one conv tail a SLOT, zeroed in-program when a row starts at position 0,
released with the slot, rebuilt by re-prefill after a preemption. What
the engine cannot give such a model yet (a mesh, int8 weights, prefix
sharing, speculative decoding) raises at construction. A model whose
layers are not all of one kind names its PATTERN (`pattern`: one period
as runs of layers, `models.qwen3_next`: three linear-attention layers to
one attention layer); the pool then holds one entry a layer with
attention (`kv_layers`) and the state one a layer with a mixer, and the
step scans periods (`ragged_step.ragged_pass`). A model with routed
experts (`routed`) hands back what its router chose with each step's
tokens, in the same fetch: a request that asks (`keep_routing`) keeps the
picks of its positions, and the step's counts ride the `serving_fetch`
span that landed it (`observability.trace.MOE_FETCH_ATTRS`). A model with
a LATENT cache (`latent`, `models.deepseek_v2`: one compressed vector and
one shared rotary key a token and layer, no heads, no V pool) sizes the
two pools by `pool_shapes`; pages, tables, refcounts, prefix sharing and
copy-on-write are the ones K and V pages have, a leading run of layers
that differs is its `prologue`, and what it cannot be given yet (a mesh,
int8 weights, a quantized pool, speculative decoding) raises at
construction. A model with WINDOWED attention layers (`windowed`,
`models.trinity_mini`: a layer attends the last `window` positions) gets a
SECOND PAGE LIFETIME: its window layers' K and V live in a pool of their
own, one ring table a row (`wtables [max_batch, nbw]`, page j in entry
j % nbw, nbw = ceil(window / bs) + ceil(chunk / bs) + 2), whose pages are
claimed at the pack of the step that first writes them and given back
once every position of theirs lies more than window - 1 behind the row's
next query AND the step that last read them has been walked (they wait on
that step's `_PackedStep.wfree`), so a page freed by step n's walk is
handed out again from step n+2. Admission RESERVES a row's most window
pages (min(nbw, its full pages)), so a claim never fails: if the free
list cannot cover a step's worst case the engine settles first
(``overlap_settles_total{reason="window"}``), which brings the pages back
that wait on the step in flight. The full layers' pages live as long as
the request, as every other model's do. What such a model cannot be given
(prefix sharing: a page given back cannot be shared; speculative decoding;
a mesh; int8 weights; a quantized pool) raises at construction.

A REQUEST'S LIFE is one record (ISSUE 38): `Request` keeps a mark where
the engine passes each point on the way to the first token (submitted,
admitted, first prompt tokens granted, last chunk dispatched, token handed
over), so its TTFT splits into queue, wait, prefill and land
(`Request.ttft_parts`). At the hand-over, inside `serving_walk`, the
record reaches both trace paths under the request's `rid`: four back-dated
collector events (`REQUEST_PHASES`) and one instant span
`serving_first_token` with the durations as attributes; a request's end is
the instant span `serving_request_end`. The scheduler's choices ride the
step's own spans: `serving_admission` closes with what it admitted and why
the head still waits, `serving_unified_dispatch` says how many resident
prefilling rows the token budget starved.

Resilience layer (ISSUE 13) — all host-side scheduler state, no compiled
program changes (flags-off the step behavior is byte-identical and the
programs lower to the same HLO):

* **Deadlines + cancellation** — ``add_request(deadline_s=)`` stamps an
  absolute expiry; every step sheds stale QUEUED requests and cancels
  expired IN-FLIGHT ones mid-generation (their pool pages freed and
  re-admittable the same step). ``Request.status`` carries the lifecycle
  (``ok | shed | cancelled | failed``).
* **Admission control + load shedding** — ``queue_max``
  (FLAGS_serving_queue_max) bounds the queue: overflow arrivals are shed
  at submit instead of growing an unbounded backlog; with deadlines
  present the queue admits earliest-deadline-first; with ``shed=True``
  (FLAGS_serving_shed) the engine watches its OWN prom TTFT recent-window
  p95 against ``ttft_slo_s`` headroom and, once the queue exceeds twice
  the slot horizon, trims it to the NEWEST ``max_batch`` arrivals — so
  overload degrades admitted-request p99 gracefully instead of
  collapsing everyone's.
* **Preempt-and-requeue** — ``preempt=True`` (FLAGS_serving_preempt):
  when the queue head cannot get pages, a decode victim is evicted
  (pages freed, request re-enqueued with prompt+generated-prefix for
  recompute; greedy replay is token-identical), so pool pressure can
  never head-of-line-block an urgent request behind a long decode.
* **Forensics** — fault-injection sites ``serving/step`` /
  ``serving/dispatch`` / ``serving/pool_exhausted`` (faults.py grammar,
  incl. hang/kill clauses), a flight-recorder serving snapshot
  (slots/queue/pool/request statuses), and a ``/healthz`` readiness
  state (``loading/ready/draining/degraded``) on the metrics server.

The crash-recovering request-replay driver lives in
:mod:`inference.resilient` (``run_serving_resilient``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.pallas.kv_append import append_tile
from ..kernels.pallas.mla_attention import shared_pages
from ..models import gpt as G
from ..observability import startup as _startup
from ..observability.trace import (
    ADMISSION_ATTRS, ADMIT_BLOCKED, CHUNK_DISPATCH_ATTRS, DISPATCH_ATTRS,
    FIRST_CALL_ATTRS, FIRST_TOKEN_ATTRS, LATENT_DISPATCH_ATTRS,
    MOE_FETCH_ATTRS, MOE_LOCAL_FETCH_ATTRS, REQUEST_END_ATTRS, REQUEST_PHASES,
    REQUEST_SPANS, SCOPES, SERVING_SPANS, SSM_DISPATCH_ATTRS, STARTUP_SPANS,
    WINDOW_DISPATCH_ATTRS)
from ..profiler.utils import RecordEvent, record_interval

__all__ = ["Request", "ServingEngine", "RunResult", "NonFiniteSampleError",
           "generate_static_batch"]

# Request.status lifecycle (terminal states besides plain completion):
#   ok        — queued / running / finished normally
#   shed      — dropped having delivered NOTHING (deadline expired in
#               queue, queue_max overflow, overload shed, draining
#               engine); a resubmission elsewhere starts from scratch
#   cancelled — dropped after delivering tokens (expired mid-generation,
#               or a preempted-and-requeued victim dropped from the
#               queue); pages freed, partial output kept
#   failed    — rejected (can never fit) or its on_token callback raised
REQUEST_STATUSES = ("ok", "shed", "cancelled", "failed")


class NonFiniteSampleError(RuntimeError):
    """The compiled step handed back a token outside [0, vocab) — the
    signature of a poisoned sampling path (nonfinite logits / corrupted
    state). Carries the rid so the resilient driver's circuit breaker can
    fail THAT request instead of retrying the whole engine forever."""

    def __init__(self, rid: int, token: int):
        super().__init__(
            f"request {rid} sampled out-of-range token {token} — "
            "nonfinite/poisoned sampling state")
        self.rid = rid
        self.token = token


def _faults():
    # lazy: the injection registry is stdlib-only, but its package pulls
    # the checkpoint/driver stack — don't pay that at serving import
    from ..distributed.resilience import faults
    return faults


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    on_token: Optional[Callable] = None  # (rid, token_id) -> None (stream)
    # scheduler state
    slot: int = -1
    prefill_done: int = 0
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # resilience (ISSUE 13): lifecycle status + absolute deadline.
    # `prompt` may GROW on preemption (emitted prefix appended for
    # recompute); `output` keeps every token ever emitted, so
    # remaining-to-emit is always max_new_tokens - len(output).
    status: str = "ok"
    error: Optional[str] = None
    deadline: Optional[float] = None    # absolute time.perf_counter()
    preemptions: int = 0
    folded: int = 0                     # output tokens already folded
    #                                     into prompt by past preemptions
    # telemetry (observability): the request's way to its first token, ONE
    # record written where each phase ends. All time.perf_counter(), each
    # set once (a preemption keeps them; `preemptions` counts the repeats):
    # submitted; `_admit` gave it a slot and its pages; the first pack that
    # granted it prompt tokens; the dispatch of the step that carries its
    # last prompt chunk (and that engine step's number); the first token's
    # hand-over in `_emit`. `ttft_s` is the last less the first, and
    # `ttft_parts()` the four phases between them.
    submit_time: float = 0.0
    admit_time: Optional[float] = None
    first_grant_time: Optional[float] = None
    prefill_end_time: Optional[float] = None
    prefill_end_step: int = 0
    first_token_time: Optional[float] = None
    ttft_s: Optional[float] = None
    # engine steps, over the request's whole life (a re-prefill after a
    # preemption counts again): granted prompt tokens; resident and
    # prefilling with a zero grant; ridden as a decode row
    prefill_steps: int = 0
    starved_steps: int = 0
    decode_steps: int = 0
    # a model with routed experts: the experts its router picked at every
    # position the engine ran, [positions, layers, k] int16 by ABSOLUTE
    # position (-1 where no pass has run yet; the token sampled last is
    # never an input), kept when the request asks (rollout replay)
    keep_routing: bool = False
    routing: Optional[np.ndarray] = None
    # prompt tokens the LAST admission found computed in shared prefix
    # pages (never run for this request; 0 without prefix sharing)
    prefix_hit_tokens: int = 0

    def marks(self):
        """The five marks on the way to the first token, in order."""
        return (self.submit_time, self.admit_time, self.first_grant_time,
                self.prefill_end_time, self.first_token_time)

    def ttft_parts(self):
        """(queue, wait, prefill, land) in seconds — submitted -> admitted
        -> first grant -> dispatch of the last prompt chunk -> first token
        handed over — or None before the first token. Each is >= 0 and
        they sum to `ttft_s`."""
        marks = self.marks()
        if None in marks:
            return None
        return tuple(b - a for a, b in zip(marks, marks[1:]))


@dataclasses.dataclass
class _PackedStep:
    """What `_pack_ragged` hands the rest of one ragged step."""
    dec: list            # decode rows (Requests), packed first
    pre: list            # prefilling rows
    ending: list         # ... of them, those whose last prompt chunk rides
    grants: dict         # slot -> prefill tokens granted this step
    props_by_slot: dict  # slot -> draft tokens riding this step
    use_spec: bool
    K: int               # burst size: passes of the program this step
    q_tokens: int        # packed query tokens (the cursor)
    kv_tokens: int       # KV positions attended over the K passes
    attn_pages: int      # (row, page) pairs the K passes' attention walks
    kv_tiles: int        # (page, tile) pairs the K passes' appends write
    starts: np.ndarray
    pos0: np.ndarray
    q_lens: np.ndarray
    emit: np.ndarray     # tokens each row emits if no EOS falls (`_advance`)
    lens_after: np.ndarray   # ... and where that leaves its context
    arrays: tuple        # the host arrays, in the program's order
    model_attrs: dict = dataclasses.field(default_factory=dict)
    #                      a recurrent or latent model's dispatch attributes
    wtables: Optional[np.ndarray] = None    # the window lifetime's ring
    #                      tables as this step reads them (a copy)
    wfree: list = dataclasses.field(default_factory=list)
    #                      window pages this step was the last to read:
    #                      given back when it has been walked
    out: tuple = ()      # once dispatched: the program's (toks, greedy_all,
    #                      lens) and, of a model with routed experts, its
    #                      (ids0, ids_burst, stats), still on the device
    #                      until the step lands


class RunResult(dict):
    """``ServingEngine.run`` return value: a plain ``{rid: output}`` dict
    plus the resilience markers — ``statuses`` ({rid: Request.status} for
    every request the run reported) and ``leftover`` (rids still queued/
    in-flight when the step budget ran out, instead of silently dropping
    them)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.statuses: Dict[int, str] = {}
        self.leftover: List[int] = []


@jax.named_scope(SCOPES.embed)
def _embed(params, tokens, pos, cfg):
    return (jnp.take(params["wte"], tokens, axis=0)
            + jnp.take(params["wpe"], pos, axis=0)).astype(cfg.dtype)


def _mm(x, p, name, cfg, out_dtype=None, psum_axis=None):
    """x @ p[name], riding the int8 MXU when the engine quantized this
    weight (W8A8 dynamic with PER-ROW activation scales — a per-tensor
    absmax would couple a request's quantization grid to its co-scheduled
    batchmates; reference: fused_multi_transformer_int8). psum_axis: set
    by row-parallel TP call sites — shares the activation scale (pmax)
    and psums the int32 accumulator so sharded int8 == dense int8."""
    wq = p.get(name + "@q")
    if wq is None:
        x = x @ p[name].astype(cfg.dtype)
        return x.astype(out_dtype) if out_dtype is not None else x
    from ..quantization import qlinear
    return qlinear(x, wq, p[name + "@s"],
                   out_dtype=out_dtype or cfg.dtype, per_row=True,
                   psum_axis=psum_axis)


def quantize_serving_params(params):
    """Per-layer, per-output-channel int8 quantization of every block
    matmul weight + the LM head; embeddings/norm vectors stay fp. The
    quantized tree swaps each weight for ('<name>@q' int8, '<name>@s'
    scales) — _mm dispatches on presence."""
    from ..quantization import quantize_to_int8
    out = dict(params)
    blocks = dict(params["blocks"])
    for name in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
        w = blocks.pop(name)  # [L, in, out] — scale per (layer, channel)
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=1, keepdims=True), 1e-8)
        q, _ = quantize_to_int8(w, scale=s)
        blocks[name + "@q"] = q
        blocks[name + "@s"] = s[:, 0, :]  # [L, out]
    out["blocks"] = blocks
    hq, hs = quantize_to_int8(params["head_w"], axis=1)
    del out["head_w"]
    out["head_w@q"] = hq
    out["head_w@s"] = hs[0]
    return out


@jax.named_scope(SCOPES.proj_mlp)
def _block_math(p, x, attn, cfg, mp_axis=None):
    """Post-attention half of the GPT block.
    mp_axis: Megatron TP inside shard_map — proj/fc2 are row-parallel
    (partial matmul + psum), fc1 column-parallel. Quantized row-parallel
    weights psum INSIDE qlinear (int32 accumulator — exact vs dense)."""
    B, S, _ = x.shape
    q_axis = mp_axis if "proj_w@q" in p else None
    out = _mm(attn.reshape(B, S, -1), p, "proj_w", cfg, psum_axis=q_axis)
    if mp_axis is not None and q_axis is None:
        with jax.named_scope(SCOPES.coll_mp):
            out = lax.psum(out, mp_axis)
    x = x + out + p["proj_b"].astype(cfg.dtype)
    h = G._ln(x, p["ln2_g"], p["ln2_b"])
    m = _mm(h.astype(cfg.dtype), p, "fc1_w", cfg) + p["fc1_b"].astype(cfg.dtype)
    m = jax.nn.gelu(m.astype(jnp.float32), approximate=True).astype(cfg.dtype)
    q_axis = mp_axis if "fc2_w@q" in p else None
    m = _mm(m, p, "fc2_w", cfg, psum_axis=q_axis)
    if mp_axis is not None and q_axis is None:
        with jax.named_scope(SCOPES.coll_mp):
            m = lax.psum(m, mp_axis)
    return x + m + p["fc2_b"].astype(cfg.dtype)


@jax.named_scope(SCOPES.qkv)
def _qkv(p, x, cfg, mp_axis=None):
    """Column-parallel under TP: the local qkv_w shard holds COMPLETE
    heads (head-major [H, heads*3*D] channel layout), so the reshape uses
    the LOCAL head count.

    The barrier keeps the product a 2-D GEMM that reads its layer out of
    the stacked weight in place. Without it the TPU compiler folds the
    reshape below INTO the product, takes the weight transposed for that
    ([heads, 3*D, H]), and so slices every layer's [H, 3H] out of the
    stack and copies it to the other layout before each GEMM (at K > 1
    the whole stack once a step): 12% of a GPT-1.3B step's device time.
    q, k and v are lane slices of the result's [.., heads, 3*D] view; the
    [.., heads, 3, D] view indexed along its 3 pads that axis to a tile
    and cost 5% of the step again (PERF.md, PR 43)."""
    B, S, _ = x.shape
    h = G._ln(x, p["ln1_g"], p["ln1_b"])
    qkv = lax.optimization_barrier(
        _mm(h.astype(cfg.dtype), p, "qkv_w", cfg)
        + p["qkv_b"].astype(cfg.dtype))
    D = cfg.head_dim
    qkv = qkv.reshape(B, S, -1, 3 * D)      # a head's columns: q | k | v
    return qkv[..., :D], qkv[..., D:2 * D], qkv[..., 2 * D:]


@jax.named_scope(SCOPES.head)
def _head_logits(params, x_last, cfg, mp_axis=None):
    """LM head on the last position; vocab-parallel under TP (local
    partial logits all-gathered — [B, V] is tiny at decode time). When
    the vocab does not divide the axis, head_w rides replicated and the
    local product is already full-width."""
    if "head_w@q" in params:
        from ..quantization import qlinear
        logits = qlinear(x_last, params["head_w@q"], params["head_w@s"],
                         out_dtype=jnp.float32, per_row=True)
    else:
        logits = x_last.astype(jnp.float32) @ params["head_w"].astype(
            jnp.float32)
    if mp_axis is not None and logits.shape[-1] < cfg.vocab_size:
        with jax.named_scope(SCOPES.coll_mp):
            logits = lax.all_gather(logits, mp_axis, axis=logits.ndim - 1,
                                    tiled=True)
    return logits


@jax.jit
def _split_key(key):
    """`key, sub = jax.random.split(key)` as one compiled call: the same
    two keys, without the eager split's unpacking slices (0.4 ms a step
    on the chip's host)."""
    both = jax.random.split(key)
    return both[0], both[1]


@jax.named_scope(SCOPES.sample)
def _sample(logits, temps, key):
    """Per-row next token: argmax where temps == 0, else a categorical
    draw at that temperature. logits: [R, V]; temps: [R]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


class GPTServing:
    """The GPT block's side of the seam between the serving step
    (`ragged_step.ragged_pass`) and a model: positions, embedding, the
    per-layer mixing (queries, and K/V for the paged pool), the post-mix
    half and the head. A configuration of another architecture names its
    own answers as ``cfg.serving_model`` (`models.falcon_h1.Serving`);
    one with ``recurrent = True`` also has a `mixer` over the packed rows
    and a per-slot state that the engine keeps beside the KV pages; one
    with ``routed = True`` has `block_math` take its run's experts whole
    (``experts=``, ``layer=``) and return (x, (ids, stats)), and says
    how many of its layers have a router (`routed_layers`) and what the
    columns of `stats` are (`route_stats`); one with
    ``latent = True`` (`models.deepseek_v2.Serving`) keeps a head-less
    latent cache (`pool_shapes`, `latent_qkv`, `attn_scale`); `prologue`
    is the run of leading layers (``params["prologue"]``, no experts)
    that comes before the periods; one with ``windowed = True``
    (`models.trinity_mini.Serving`) has runs of kind "window" beside
    "attention" (`qkv` is then told the ``kind``), whose K and V pages
    live in a second pool with a lifetime of their own (`window`,
    `window_layers`). The kinds of run: "attention", "window", "parallel",
    "linear", "latent"."""

    recurrent = False
    routed = False      # no router: `block_math` returns the stream alone
    latent = False

    @staticmethod
    def prologue(cfg):
        """(kind, count) of the layers before the first period."""
        return ("attention", 0)

    @staticmethod
    def pattern(cfg):
        """One period of the layer pattern, as runs (kind, count)."""
        return (("attention", 1),)

    @staticmethod
    def kv_layers(cfg):
        """Layers that keep K and V in the paged pool."""
        return cfg.num_layers

    @staticmethod
    def positions(pos, cfg):
        return jnp.minimum(pos, cfg.max_seq_len - 1)   # inside the table

    embed = staticmethod(_embed)

    @staticmethod
    def qkv(p, x, pos, cfg, mp_axis=None):
        return _qkv(p, x, cfg, mp_axis) + (None,)

    @staticmethod
    def block_math(p, x, attn, mixed, cfg, mp_axis=None):
        return _block_math(p, x, attn, cfg, mp_axis)

    @staticmethod
    def final_norm(params, x, cfg):
        return G._ln(x, params["lnf_g"], params["lnf_b"])

    head_logits = staticmethod(_head_logits)


def serving_model(cfg):
    """The seam's functions for a configuration."""
    return getattr(cfg, "serving_model", GPTServing)


def _settled_view(name):
    """`slots`, `lens` and the device state as an outsider reads (or
    replaces) them: the step in flight owns the donated buffers and runs
    ahead of `Request.output`, so an outside read settles first. The
    engine's own code uses the private names."""
    private = "_" + name

    def get(self):
        self.settle("observer")
        return getattr(self, private)

    def put(self, value):
        self.settle("observer")
        setattr(self, private, value)
    return property(get, put, doc=_settled_view.__doc__)


class ServingEngine:
    """Continuous-batching engine over a paged KV pool (see module doc)."""
    @RecordEvent(STARTUP_SPANS.engine, "Startup")   # see PERF.md, PR 56
    def __init__(self, params, cfg: G.GPTConfig, *, max_batch: int = 4,
                 block_size: int = None, num_blocks: int = 256,
                 max_blocks_per_seq: int = 32, chunk: int = None,
                 decode_burst: int = None, seed: int = 0, mesh=None,
                 mp_axis: str = "mp", int8: bool = False, ragged=None,
                 kv_cache_dtype=None,
                 kv_pool_bytes: Optional[int] = None,
                 token_budget: Optional[int] = None, adaptive_mix=None,
                 ttft_slo_s: Optional[float] = None, queue_max=None,
                 shed=None, shed_headroom: float = 0.5, preempt=None,
                 preempt_wait_steps: int = 2, prefix_share=None,
                 spec_decode_k=None, proposer=None, pool_audit=None,
                 ssm_state_dtype="float32",
                 num_window_blocks: Optional[int] = None):
        from ..flags import flag
        from ..enforce import enforce
        block_size = (int(flag("paged_block_size")) if block_size is None
                      else block_size)
        chunk = (int(flag("serving_prefill_chunk")) if chunk is None
                 else chunk)
        decode_burst = (int(flag("serving_decode_burst"))
                        if decode_burst is None else decode_burst)
        # `ragged` selects nothing: the benchmark's runners pass
        # ragged=True and may not be edited by the PR that removed the
        # other path; it goes once they drop the word (ROADMAP Queue 3)
        enforce(ragged is None or ragged is True,
                f"ragged={ragged!r}: the engine has one step, the "
                "single-dispatch ragged one; the two-program path was "
                "removed in PR 30. Pass ragged=True or leave it out",
                op="ServingEngine")
        if kv_cache_dtype is None:
            kv_cache_dtype = str(flag("serving_kv_cache_dtype"))
        if adaptive_mix is None or adaptive_mix == "auto":
            adaptive_mix = bool(flag("serving_adaptive_mix"))
        from ..quantization.kv_cache import (kv_cache_dtype as _kv_dtype,
                                             kv_pool_blocks_for_budget)
        if kv_cache_dtype == "auto":
            pool_dtype, kv_quantized = cfg.dtype, False
        else:
            pool_dtype, kv_quantized = _kv_dtype(kv_cache_dtype)
        self.model = serving_model(cfg)
        # the pool holds one entry a layer WITH attention
        L, D = self.model.kv_layers(cfg), cfg.head_dim
        Hkv = getattr(cfg, "num_kv_heads", cfg.num_heads)
        if prefix_share is None or prefix_share == "auto":
            prefix_share = bool(flag("serving_prefix_share"))
        self.prefix_share = bool(prefix_share)
        if spec_decode_k is None or spec_decode_k == "auto":
            spec_decode_k = int(flag("serving_spec_decode_k"))
        self.spec_k = max(int(spec_decode_k), 0)
        # -- a model with a recurrent mixer: its state and conv tail live
        # beside the KV pages, one entry a SLOT (not a page), zeroed
        # in-program when a row starts at position 0 and rebuilt by
        # re-prefill after a preemption. What the engine cannot give such
        # a model yet is refused here, not served some other way.
        if self.model.recurrent:
            for ok, what in (
                    (mesh is None, "a mesh: the mixer is not sharded"),
                    (not int8, "int8 weights: the mixer's leaves have no "
                               "quantized form"),
                    (not self.prefix_share,
                     "prefix_share: a shared page has no state to go "
                     "with it"),
                    (self.spec_k == 0,
                     "spec_decode_k > 0: a rejected draft would have to "
                     "roll the state back")):
                enforce(ok, "a model with a recurrent state cannot be "
                            f"served with {what}", op="ServingEngine")
            enforce(chunk <= cfg.ssm_chunk,
                    f"chunk {chunk} must not pass the mixer's scan chunk "
                    f"{cfg.ssm_chunk}", op="ServingEngine")
        # -- a model with a latent cache: its pages have no heads and the
        # two pools differ in width (`pool_shapes`); everything that deals
        # in pages is as it was
        self._latent = self.model.latent
        pools = ((Hkv, D), (Hkv, D))
        if self._latent:
            for ok, what in (
                    (mesh is None, "a mesh: a page has no heads to shard"),
                    (not int8, "int8 weights: its leaves have no quantized "
                               "form"),
                    (not kv_quantized,
                     f"kv_cache_dtype={kv_cache_dtype!r}: the latent "
                     "append and its attention have no quantized page"),
                    (self.spec_k == 0,
                     "spec_decode_k > 0: the verify pass is not built for "
                     "its attention")):
                enforce(ok, "a model with a latent cache cannot be served "
                            f"with {what}", op="ServingEngine")
            pools = self.model.pool_shapes(cfg)
        # -- a model with windowed layers: a second page lifetime (module
        # doc). What the engine cannot give it is refused here
        self._windowed = bool(getattr(self.model, "windowed", False))
        if self._windowed:
            for ok, what in (
                    (mesh is None, "a mesh: the window pool is not sharded"),
                    (not int8, "int8 weights: its leaves have no quantized "
                               "form"),
                    (not self.prefix_share,
                     "prefix_share: a page that was given back cannot be "
                     "shared"),
                    (self.spec_k == 0,
                     "spec_decode_k > 0: a rejected draft would have to "
                     "take back pages the window gave up"),
                    (not kv_quantized,
                     f"kv_cache_dtype={kv_cache_dtype!r}: the window "
                     "lifetime's append has no quantized page")):
                enforce(ok, "a model with windowed layers cannot be served "
                            f"with {what}", op="ServingEngine")
            enforce(decode_burst <= block_size,
                    f"decode_burst {decode_burst} must not pass a page's "
                    f"{block_size} positions: the window ring has one page "
                    "of room for a step's burst", op="ServingEngine")
        if kv_pool_bytes is not None and self._latent:
            num_blocks = max(2, int(kv_pool_bytes // (
                L * block_size * sum(h * d for h, d in pools)
                * jnp.dtype(pool_dtype).itemsize)))
        elif kv_pool_bytes is not None:
            # capacity from a fixed HBM byte budget: the int8-pool mode
            # admits ~2x the blocks of bf16 at the same budget
            num_blocks = max(2, kv_pool_blocks_for_budget(
                kv_pool_bytes, L, Hkv, block_size, D, pool_dtype))
        if int8:
            # W8A8 decode: weights stored int8 with per-output-channel
            # scales; decode reads every weight per token, so halving the
            # bytes attacks its memory-bound cost directly. Under TP the
            # scales shard with their weight's output channels (_tp_shard).
            params = quantize_serving_params(params)
        self.params, self.cfg = params, cfg
        self.bs, self.chunk = block_size, chunk
        self.max_batch = max_batch
        self.kv_quantized = kv_quantized
        # rows of the tile the in-place append writes; a quantized pool
        # requantizes whole pages instead
        self._append_tile = (0 if kv_quantized
                             else append_tile(pool_dtype, block_size))
        # device state and the slot list are private: the step in flight
        # owns them (the buffers are donated to it), and an outsider reads
        # them through the settling views at the end of the class
        self._k_pools, self._v_pools = (
            jnp.zeros((L, h, num_blocks, block_size, d), pool_dtype)
            for h, d in pools)
        self._wk_pools = self._wv_pools = self.wtables = None
        self._nbw = self._num_wblocks = 0
        self.window_pages_freed = self._wfreed_reported = 0
        if self._windowed:
            # the second lifetime: one ring table a row, block 0 scratch
            self._window = int(self.model.window(cfg))
            self._nbw = (-(-self._window // block_size)
                         + -(-chunk // block_size) + 2)
            if num_window_blocks is None:
                num_window_blocks = max_batch * self._nbw + 1
            self._num_wblocks = int(num_window_blocks)
            self._wk_pools, self._wv_pools = (
                jnp.zeros((self.model.window_layers(cfg), Hkv,
                           self._num_wblocks, block_size, D), pool_dtype)
                for _ in range(2))
            self.wtables = np.zeros((max_batch, self._nbw), np.int32)
            self.wfree_blocks = list(range(self._num_wblocks - 1, 0, -1))
            # per slot: the logical pages [lo, hi) its ring holds, and the
            # pages admission reserved for it
            self._wlo = np.zeros((max_batch,), np.int64)
            self._whi = np.zeros((max_batch,), np.int64)
            self._wreserved = np.zeros((max_batch,), np.int64)
        self._k_scales = self._v_scales = None
        if kv_quantized:
            self._k_scales = jnp.zeros((L, Hkv, num_blocks), jnp.float32)
            self._v_scales = jnp.zeros_like(self._k_scales)
        self.tables = np.zeros((max_batch, max_blocks_per_seq), np.int32)
        self._lens = np.zeros((max_batch,), np.int32)   # COMMITTED (walked)
        # block 0 is the scratch block idle slots write into
        self.free_blocks = list(range(num_blocks - 1, 0, -1))
        # -- prefix page sharing + speculative decoding (ISSUE 17).
        # Refcounted pool: every allocated page carries a holder count;
        # block tables may reference the same page from several rows.
        # Flags-off the refcounts are all 0/1 and every path below
        # degenerates to the pre-sharing behavior byte-for-byte.
        if proposer is None:
            from .speculative import ngram_propose
            proposer = ngram_propose
        self._proposer = proposer
        if pool_audit is None or pool_audit == "auto":
            pool_audit = bool(flag("serving_pool_audit"))
        self.pool_audit = bool(pool_audit)
        self._ssm_state = self._conv_tail = None
        self.ssm_resets = self._ssm_resets_reported = 0
        # -- a model with routed experts: what the router chose comes back
        # with each step's tokens. Totals since construction, and per
        # layer the sum over passes of largest / mean assignments a held
        # expert (`moe_passes` of them with any assignment)
        self.moe_experts_touched = self.moe_assignments = 0
        self.moe_passes = 0
        self.moe_local_tokens = self.moe_tokens = 0  # a group-limited router
        self.prefix_hit_tokens = 0      # prompt tokens found in shared pages
        self.cache_evictions = 0        # cached-free pages taken for new ones
        if self.model.routed:
            lo, hi = cfg.experts_held
            self._moe_held = hi - lo
            # the layers WITH a router (a prologue has none)
            self._routed_layers = self.model.routed_layers(cfg)
            self._moe_load = np.zeros((self._routed_layers,), np.float64)
        if self.model.recurrent:
            state_shape, tail_shape = self.model.state_shapes(cfg,
                                                              max_batch)
            self._ssm_state = jnp.zeros(state_shape,
                                        jnp.dtype(ssm_state_dtype))
            self._conv_tail = jnp.zeros(tail_shape, cfg.dtype)
        self.refcount = np.zeros((num_blocks,), np.int32)
        # page-granular prefix cache: chained page hash -> resident block
        # (and the reverse index). Pages whose last holder left stay
        # addressable in the cached-free LRU until evicted for allocation.
        self._prefix_cache: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}
        from collections import OrderedDict
        self._cached_free: "OrderedDict[int, bool]" = OrderedDict()
        # first-page hash -> rid of a still-prefilling owner: queued
        # siblings (n>1 fan-out) defer admission until the owner's pages
        # are computed, then share them instead of recomputing
        self._prefix_pending: Dict[bytes, int] = {}
        # copy-on-write pairs (src, dst) scheduled by admission and
        # executed IN-PROGRAM by the next dispatch (one-dispatch contract)
        self._cow_pairs: List = []
        self._reset_tables = np.zeros_like(self.tables)
        self.cow_copies = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._cow_reported = 0
        self._spec_prop_reported = 0
        self._spec_acc_reported = 0
        self._slots: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self._next_rid = 0
        self._key = jax.random.PRNGKey(seed)
        self.decode_burst = decode_burst
        # fixed per-step token budget shared by decode rows (1 each,
        # always granted) and prefill chunks (the leftover)
        self.token_budget = (int(token_budget) if token_budget
                             else max_batch + chunk)
        enforce(self.token_budget >= max_batch,
                f"token_budget {self.token_budget} must cover one decode "
                f"token per slot (max_batch {max_batch})",
                op="ServingEngine")
        self._c_att = max(1, min(chunk, self.token_budget))
        self.adaptive_mix = adaptive_mix
        self.ttft_slo_s = ttft_slo_s
        # -- resilience (ISSUE 13): admission control + shed/preempt policy.
        # All host-side scheduler state; flags-off none of it changes the
        # compiled programs or the step-for-step behavior.
        if queue_max is None or queue_max == "auto":
            queue_max = int(flag("serving_queue_max"))
        self.queue_max = int(queue_max)          # 0 = unbounded
        if shed is None or shed == "auto":
            shed = bool(flag("serving_shed"))
        self.shed_on_overload = bool(shed)
        self.shed_headroom = float(shed_headroom)
        if preempt is None or preempt == "auto":
            preempt = bool(flag("serving_preempt"))
        self.preempt = bool(preempt)
        self.preempt_wait_steps = max(int(preempt_wait_steps), 1)
        self._hol_wait_steps = 0   # consecutive steps the queue head was
        #                            pool-blocked (preemption trigger)
        self._blocked = ADMIT_BLOCKED.none  # why the head waited at the
        #                            last `_admit` (the admission span's)
        self.preempted = 0         # decode victims evicted and requeued
        self.draining = False
        self._health = "loading"
        # terminal transitions that happen OUTSIDE a step (shed at submit)
        # are queued here and reported by the next step()/run() so no
        # request ever silently vanishes
        self._notify: List[Request] = []
        # SLO pressure reads the prom registry's recent-window p95 (16
        # samples), not the exported summary's lifetime mean — one
        # compile-heavy startup wave must not pin the adaptive mix at
        # shortened bursts for the engine's whole life, and a p95 SLO is
        # what the fleet router will compare across replicas
        self._ttft_window = 16
        # dispatch accounting (the contract is ONE compiled dispatch per
        # engine step; the benchmark reports dispatches/step)
        self.dispatches = 0
        self.engine_steps = 0
        self._jit_programs: List = []
        self.decode_microsteps = 0  # device decode steps issued (telemetry)
        self._pending_tok = np.zeros((max_batch,), np.int32)
        # -- one step in flight (ISSUE 31): the step dispatched last and
        # not yet fetched (a `_PackedStep` with its `out`), and the token
        # each slot emitted last, which stays on the device between steps
        self._flight: Optional[_PackedStep] = None
        self._last_tok = jnp.zeros((max_batch,), jnp.int32)
        # -- observability: per-engine Prometheus registry (TTFT, tokens/s,
        # queue depth, KV-pool utilization, decode/prefill mix). Pure host
        # floats updated inside step() — a scrape never adds a dispatch.
        from ..observability import PromRegistry
        self._num_blocks = num_blocks
        self._prom = PromRegistry(namespace="paddle_tpu_serving")
        for name, text in (
                ("steps_overlapped_total",
                 "steps dispatched while the one before was still in "
                 "flight"),
                ("overlap_settles_total",
                 "steps fetched and walked before the next was packed, by "
                 "what needed their result"),
                ("overlap_wasted_rows_total",
                 "rows that rode one more step after they had ended (an "
                 "EOS is learnt a step late)")):
            self._prom.counter_inc(name, 0, help=text)
        self._metrics_server = None
        self._t_first_step: Optional[float] = None
        self._tokens_total = 0
        # crash forensics: flight-recorder bundles include a serving
        # snapshot (slots/queue/pool/request statuses) of every live
        # engine — weak registration, same contract as TelemetryHost
        from ..observability.flight_recorder import register_serving_engine
        register_serving_engine(self)
        # -- numerics: KV-pool page-scale drift (ISSUE 15). A quantized
        # pool's per-page running-absmax scales only ever GROW while a
        # page is live; sustained growth means every append requantizes
        # old tokens onto a coarser grid. Host-side only (one bounded
        # device fetch per telemetry interval, OUTSIDE the compiled
        # program) — flags-off behavior stays byte-identical.
        from ..flags import flag as _flag
        self._numerics_kv = (bool(_flag("numerics"))
                             and self._k_scales is not None)
        self._numerics_kv_interval = max(int(_flag("telemetry_interval")),
                                         1)
        self._numerics_kv_prev: Optional[Dict[str, np.ndarray]] = None
        self._numerics_kv_last: Optional[Dict[str, float]] = None
        # per-page allocation generation (bumped at admission): the
        # drift poll uses it to exclude pages freed + re-admitted
        # between two polls from the "requantized" count
        self._numerics_kv_gen = np.zeros((num_blocks,), np.int64)

        # params ride as ARGUMENTS (a closure would bake 4 bytes/param
        # into the serialized HLO — megabytes that also defeat donation)
        self._mesh = mesh
        self._mp_axis = mp_axis if mesh is not None else None
        if mesh is not None:
            self._tp_shard(mesh, mp_axis)
        # the unified programs compile lazily per burst length K (only
        # the sizes the scheduler asks for)
        self._unified_cache = {}

    @staticmethod
    def _burst_sizes(k_max):
        ks = [1]
        while ks[-1] < k_max:
            ks.append(min(ks[-1] * 2, k_max))
        return ks

    def _tp_shard(self, mesh, mp_axis):
        """Shard params + KV pools over the mp mesh axis (Megatron TP):
        qkv/fc1 column-parallel (complete local heads), proj/fc2
        row-parallel, vocab-parallel head when the vocab divides the
        axis. int8 weight storage shards exactly like the weight it
        replaces, and the per-output-channel scales FOLLOW the output
        channels: column-parallel scales shard on out, row-parallel
        scales stay replicated (out dim unsharded)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = self.cfg
        ax = mp_axis
        n = mesh.shape[mp_axis]
        from ..enforce import enforce
        enforce(cfg.num_heads % n == 0 and cfg.ffn_hidden % n == 0,
                f"TP serving needs heads ({cfg.num_heads}) and ffn "
                f"({cfg.ffn_hidden}) divisible by the {mp_axis} axis "
                f"({n})", op="ServingEngine")
        # vocab-parallel head only when the vocab divides the axis
        head_spec = P(None, ax) if cfg.vocab_size % n == 0 else P()
        block_specs = {
            "ln1_g": P(), "ln1_b": P(),
            "qkv_w": P(None, None, ax), "qkv_b": P(None, ax),
            "proj_w": P(None, ax, None), "proj_b": P(),
            "ln2_g": P(), "ln2_b": P(),
            "fc1_w": P(None, None, ax), "fc1_b": P(None, ax),
            "fc2_w": P(None, ax, None), "fc2_b": P(),
            "qkv_w@q": P(None, None, ax), "qkv_w@s": P(None, ax),
            "fc1_w@q": P(None, None, ax), "fc1_w@s": P(None, ax),
            "proj_w@q": P(None, ax, None), "proj_w@s": P(),
            "fc2_w@q": P(None, ax, None), "fc2_w@s": P(),
        }
        pspec = {
            "wte": P(), "wpe": P(),
            "blocks": {k: block_specs[k] for k in self.params["blocks"]},
            "lnf_g": P(), "lnf_b": P(),
        }
        if "head_w" in self.params:
            pspec["head_w"] = head_spec
        else:
            pspec["head_w@q"] = head_spec
            pspec["head_w@s"] = (P(ax) if cfg.vocab_size % n == 0
                                 else P())
        pool_spec = P(None, ax)
        self.params = jax.tree.map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
            self.params, pspec)
        self._k_pools = jax.device_put(self._k_pools,
                                       NamedSharding(mesh, pool_spec))
        self._v_pools = jax.device_put(self._v_pools,
                                       NamedSharding(mesh, pool_spec))
        if self.kv_quantized:
            self._k_scales = jax.device_put(self._k_scales,
                                            NamedSharding(mesh, pool_spec))
            self._v_scales = jax.device_put(self._v_scales,
                                            NamedSharding(mesh, pool_spec))
        self._last_tok = jax.device_put(self._last_tok,
                                        NamedSharding(mesh, P()))
        self._tp_pspec, self._tp_pool_spec = pspec, pool_spec

    # -- the unified program (ISSUE 6) ---------------------------------------
    def _unified(self, K, spec=False):
        """The ONE compiled program for a ragged step with a K-token
        decode burst (lazily built per K — only scheduler-chosen sizes
        compile; the spec-verify variant, which returns the argmax at
        every packed position, is its own entry). Calling convention
        matches ragged_step.unified_step with the pools (and scales,
        when quantized) donated."""
        key = (K, spec)
        fn = self._unified_cache.get(key)
        if fn is None:
            fn = self._build_unified(K, spec)
            self._unified_cache[key] = fn
        return fn

    def _build_unified(self, K, spec=False):
        from . import ragged_step as RS
        cfg, bsz, c_att = self.cfg, self.bs, self._c_att
        quant = self.kv_quantized
        share = self.prefix_share
        mesh, ax = self._mesh, self._mp_axis
        if self.model.recurrent:
            # pools, scales (None unless quantized), state, tail: donated
            jfn = jax.jit(functools.partial(
                RS.unified_step, cfg=cfg, bs=bsz, c_att=c_att, K=K),
                donate_argnums=(15, 16, 17, 18, 22, 23))
            self._jit_programs.append(jfn)
            return jfn
        if self._windowed:
            # both lifetimes' pools donated (unified_step's full argument
            # list: no scales, no copy-on-write, no recurrent state)
            jfn = jax.jit(functools.partial(
                RS.unified_step, cfg=cfg, bs=bsz, c_att=c_att, K=K),
                donate_argnums=(15, 16, 25, 26))
            self._jit_programs.append(jfn)
            return jfn
        if mesh is None:
            if quant:
                # positional passthrough: with prefix sharing on, the
                # engine appends (cow_src, cow_dst, reset_tables)
                jfn = jax.jit(functools.partial(
                    RS.unified_step, cfg=cfg, bs=bsz, c_att=c_att, K=K,
                    spec=spec),
                    donate_argnums=(15, 16, 17, 18))
                self._jit_programs.append(jfn)
                return jfn

            if share:
                def fn(params, tokens, row_of, off_of, starts, pos0,
                       q_lens, tables, fresh, sample0, remaining,
                       eos_ids, temps, prev_tok, key, kp, vp, cow_src,
                       cow_dst, reset_tables):
                    return RS.unified_step(
                        params, tokens, row_of, off_of, starts, pos0,
                        q_lens, tables, fresh, sample0, remaining,
                        eos_ids, temps, prev_tok, key, kp, vp, None, None,
                        cow_src, cow_dst, reset_tables, cfg=cfg, bs=bsz,
                        c_att=c_att, K=K, spec=spec)
            else:
                def fn(params, tokens, row_of, off_of, starts, pos0,
                       q_lens, tables, fresh, sample0, remaining,
                       eos_ids, temps, prev_tok, key, kp, vp):
                    return RS.unified_step(
                        params, tokens, row_of, off_of, starts, pos0,
                        q_lens, tables, fresh, sample0, remaining,
                        eos_ids, temps, prev_tok, key, kp, vp, None, None,
                        cfg=cfg, bs=bsz, c_att=c_att, K=K, spec=spec)

            jfn = jax.jit(fn, donate_argnums=(15, 16))
            self._jit_programs.append(jfn)
            return jfn

        # TP: the unified program runs inside shard_map over the
        # shardings _tp_shard placed (pools/scales head-sharded,
        # descriptors replicated)
        from jax.sharding import PartitionSpec as P
        from ..utils import shard_map
        pspec, pool_spec = self._tp_pspec, self._tp_pool_spec
        rep = P()

        if quant:
            def fn(params, tokens, row_of, off_of, starts, pos0, q_lens,
                   tables, fresh, sample0, remaining, eos_ids, temps,
                   prev_tok, key_data, kp, vp, ks, vs, *extra):
                return RS.unified_step(
                    params, tokens, row_of, off_of, starts, pos0, q_lens,
                    tables, fresh, sample0, remaining, eos_ids, temps,
                    prev_tok, jax.random.wrap_key_data(key_data), kp, vp,
                    ks, vs, *extra, cfg=cfg, bs=bsz, c_att=c_att, K=K,
                    spec=spec, mp_axis=ax)
            in_specs = (pspec,) + (rep,) * 14 + (pool_spec,) * 4
            out_specs = ((rep,) * (2 if spec else 1)
                         + (pool_spec,) * 4 + (rep, rep))
            donate = (15, 16, 17, 18)
        else:
            def fn(params, tokens, row_of, off_of, starts, pos0, q_lens,
                   tables, fresh, sample0, remaining, eos_ids, temps,
                   prev_tok, key_data, kp, vp, *extra):
                out = RS.unified_step(
                    params, tokens, row_of, off_of, starts, pos0, q_lens,
                    tables, fresh, sample0, remaining, eos_ids, temps,
                    prev_tok, jax.random.wrap_key_data(key_data), kp, vp,
                    None, None, *extra, cfg=cfg, bs=bsz, c_att=c_att, K=K,
                    spec=spec, mp_axis=ax)
                # the unquantized pool has no scales to shard
                return tuple(o for o in out if o is not None)
            in_specs = (pspec,) + (rep,) * 14 + (pool_spec, pool_spec)
            out_specs = ((rep,) * (2 if spec else 1)
                         + (pool_spec, pool_spec, rep, rep))
            donate = (15, 16)
        if share:
            in_specs = in_specs + (rep, rep, rep)

        jfn = jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs),
                      donate_argnums=donate)
        self._jit_programs.append(jfn)

        n_head = 2 if spec else 1     # toks (and greedy_all) lead

        def call(*a):
            a = list(a)
            a[14] = jax.random.key_data(a[14])  # PRNG key position
            out = jfn(*a)
            if quant:
                return out
            *head, kp, vp, lens, last_tok = out
            assert len(head) == n_head
            return (*head, kp, vp, None, None, lens, last_tok)
        return call

    def compiled_cache_entries(self) -> int:
        """Total traced-program cache entries across every jit program
        this engine built — the one-dispatch-per-step contract is
        asserted against this in tests (and reported by the serving
        bench)."""
        return sum(f._cache_size() for f in self._jit_programs)

    def _pick_burst(self, n_prefilling: int) -> int:
        """Adaptive prefill/decode mix, driven by the queue-depth and
        TTFT series the Prometheus registry exports: under admission
        pressure (waiting queue / active prefills / TTFT above the SLO)
        the decode burst shortens so prefill slices come around more
        often per wall-clock; with no pressure the burst runs long to
        amortize dispatch. Fixed `decode_burst` when adaptive_mix off."""
        if not self.adaptive_mix:
            return self.decode_burst
        q_depth = int(self._prom.get("queue_depth") or 0)
        pressure = q_depth + n_prefilling
        # recent-window p95, NOT the summary's lifetime mean: the mean
        # never decays, so one slow startup wave would halve bursts
        # forever; p95 (vs the window mean) is the tail the SLO names
        ttft = self._prom.quantile("ttft_seconds", 0.95)
        if (self.ttft_slo_s is not None and ttft is not None
                and ttft > self.ttft_slo_s):
            pressure = max(pressure * 2, 1)
        if pressure <= 0:
            return self.decode_burst
        k = max(1, self.decode_burst // (pressure + 1))
        return max(s for s in self._burst_sizes(self.decode_burst)
                   if s <= k)

    # -- public --------------------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int, temperature=0.0,
                    eos_id=None, on_token=None,
                    deadline_s: Optional[float] = None,
                    keep_routing: bool = False) -> int:
        """Submit a request. deadline_s: seconds from NOW the caller is
        willing to wait for completion — past it the scheduler sheds the
        request from the queue or cancels it mid-generation (pages
        freed). A draining or full-queue engine sheds at submit; the shed
        request is still reported by the next step()/run() with
        ``status='shed'``."""
        rid = self._next_rid
        self._next_rid += 1
        r = Request(rid, np.asarray(prompt, np.int32),
                    int(max_new_tokens), temperature, eos_id, on_token)
        r.submit_time = time.perf_counter()
        if keep_routing:
            from ..enforce import enforce
            enforce(self.model.routed, "keep_routing: this model has no "
                    "router", op="ServingEngine.add_request")
            r.keep_routing = True
            r.routing = np.full(
                (len(r.prompt) + r.max_new_tokens, self._routed_layers,
                 self.cfg.experts_per_tok), -1, np.int16)
        if deadline_s is not None:
            r.deadline = r.submit_time + float(deadline_s)
        self._prom.counter_inc("requests_total",
                               help="requests ever submitted")
        if self.draining:
            self._shed(r, "draining")
            self._notify.append(r)
            return rid
        if self.queue_max and len(self.queue) >= self.queue_max:
            # bounded queue: shedding the ARRIVAL keeps the backlog (and
            # every queued request's TTFT) bounded under overload
            self._shed(r, "queue_full")
            self._notify.append(r)
            return rid
        self.queue.append(r)
        self._prom.gauge_set("queue_depth", len(self.queue),
                             help="requests waiting for a slot")
        self._emit_event("serving_submit", rid=rid,
                         prompt_len=len(r.prompt),
                         max_new_tokens=r.max_new_tokens,
                         deadline_s=deadline_s,
                         queue_depth=len(self.queue))
        return rid

    def has_work(self) -> bool:
        """Anything queued, running, or dispatched and not yet walked."""
        return (bool(self.queue) or self._flight is not None
                or any(s is not None for s in self._slots))

    def settle(self, reason: str = "observer") -> None:
        """Fetch and walk the step in flight; a no-op with nothing in
        flight. Afterwards `Request.output`, `prefill_done`, `lens`, the
        pool's accounting and the device state all describe the same,
        last dispatched step. Tokens go through `_emit`/`on_token` as in
        any walk; requests that reach a terminal state wait in `_notify`
        for the next `step()`. The engine calls it itself wherever its
        next decision needs the result (`reason`, counted in
        ``overlap_settles_total``): `spec` (the proposer reads the
        output), `preempt`, `cancel`, `expire`, `drain` (the host removes
        or rewinds a running row), `tail` (nothing left to pack),
        `observer` (an outside read of `slots` or of a device-state
        view)."""
        f, self._flight = self._flight, None
        if f is None:
            return
        self._prom.counter_inc("overlap_settles_total",
                               labels={"reason": reason})
        self._notify.extend(self._land(f))

    def run(self, max_steps: int = 100000) -> "RunResult":
        """Drive to completion; returns {rid: output token ids} (a
        :class:`RunResult`: ``.statuses`` maps each reported rid to its
        lifecycle status, and when the step budget runs out with work
        left the survivors land in ``.leftover`` — reported loudly
        (``serving_steps_exhausted`` event + counter) instead of being
        silently dropped)."""
        results = RunResult()

        def take(reqs):
            for r in reqs:
                results[r.rid] = r.output
                results.statuses[r.rid] = r.status
        take(self._take_notifications())
        for _ in range(max_steps):
            if not self.has_work():
                break
            take(self.step())
        self.settle("tail")     # a budget that ran out mid-flight
        take(self._take_notifications())
        if self.has_work():
            leftover = ([r.rid for r in self.queue]
                        + [s.rid for s in self._slots if s is not None])
            results.leftover = sorted(leftover)
            self._prom.counter_inc(
                "run_steps_exhausted_total",
                help="run() budgets that ran out with work left")
            self._emit_event("serving_steps_exhausted",
                             max_steps=max_steps,
                             leftover=results.leftover)
        return results

    # -- resilience surface (ISSUE 13) ---------------------------------------
    @property
    def health(self) -> str:
        """Readiness state for /healthz: ``loading`` (no completed step
        yet), ``ready``, ``draining`` (SIGTERM drain — finishing, not
        admitting), ``degraded`` (driver-set during rebuild/overload)."""
        return self._health

    def set_health(self, state: str) -> None:
        from ..enforce import enforce
        enforce(state in ("loading", "ready", "draining", "degraded"),
                f"unknown health state {state!r}", op="ServingEngine")
        self._health = state

    def drain(self) -> None:
        """Enter drain mode (the SIGTERM endgame): stop admitting — both
        from the queue and at submit — and let in-flight requests finish.
        The resilient driver pairs this with :meth:`shed_queue` and, at
        grace expiry, :meth:`cancel_all`."""
        if not self.draining:
            self.settle("drain")
            self.draining = True
            self._health = "draining"
            self._emit_event("serving_drain", queue_depth=len(self.queue),
                             running=sum(s is not None
                                         for s in self._slots))

    def shed_queue(self, reason: str = "draining") -> List[Request]:
        """Shed every queued (not yet started) request; returns them so a
        driver can requeue elsewhere. In-flight requests are untouched."""
        out, self.queue = self.queue, []
        for r in out:
            self._shed(r, reason)
        self._notify.extend(out)
        self._prom.gauge_set("queue_depth", 0)
        return out

    def cancel(self, rid: int, reason: str = "cancelled"
               ) -> Optional[Request]:
        """Cancel one request wherever it is (queued -> shed, in-flight ->
        pages freed); returns the Request, or None if unknown/finished."""
        for r in list(self.queue):
            if r.rid == rid:
                self.queue.remove(r)
                self._shed(r, reason)
                self._notify.append(r)
                return r
        if any(r is not None and r.rid == rid for r in self._slots):
            self.settle("cancel")   # it may end in the step in flight
        for r in self._slots:
            if r is not None and r.rid == rid:
                self._cancel(r, reason)
                self._notify.append(r)
                return r
        return None

    def cancel_all(self, reason: str = "cancelled") -> List[Request]:
        """Cancel everything (queued + in-flight); returns the requests.
        The drain-deadline endgame: pages all return to the pool."""
        out = self.shed_queue(reason)
        self.settle("cancel")
        for r in list(self._slots):
            if r is not None:
                self._cancel(r, reason)
                self._notify.append(r)
                out.append(r)
        return out

    def load_stats(self) -> Dict[str, float]:
        """Placement read for a fleet router (ISSUE 16): pending work
        (queue + running), recent-window TTFT p95 and KV-pool
        utilization, straight off the engine's own prom registry — host
        floats only, the device is never touched."""
        pending = (len(self.queue)
                   + sum(1 for s in self._slots if s is not None))
        return {
            "pending": float(pending),
            "ttft_p95": float(self._prom.quantile("ttft_seconds", 0.95)
                              or 0.0),
            "pool_utilization": float(
                self._prom.get("kv_pool_utilization") or 0.0),
            # the window layers' pool, claimed and given back as the
            # windows slide (0.0 for a model without windowed layers)
            "window_pool_utilization": self._window_utilization(),
            # sharing/speculation health (ISSUE 17): pages referenced by
            # >1 block table, COW copies, and the spec acceptance pair —
            # acceptance/proposed IS the speculation health metric
            "kv_pages_shared": float(int((self.refcount > 1).sum())),
            "kv_cow_copies_total": float(self.cow_copies),
            "spec_proposed_total": float(self.spec_proposed),
            "spec_accepted_total": float(self.spec_accepted),
        }

    def snapshot(self) -> Dict:
        """Host-state serving snapshot for flight-recorder bundles:
        slots, queue, pool utilization, health — cheap, never touches
        the device and never settles: `emitted` and `prefill_done` are
        COMMITTED progress, one step behind what is dispatched."""
        total = self._num_blocks - 1

        def req(r):
            return {"rid": r.rid, "status": r.status,
                    "prompt_len": int(len(r.prompt)),
                    "emitted": len(r.output),
                    "prefill_done": int(r.prefill_done),
                    "max_new_tokens": int(r.max_new_tokens),
                    "deadline_in_s": (
                        None if r.deadline is None
                        else round(r.deadline - time.perf_counter(), 3)),
                    "preemptions": r.preemptions}
        return {
            "health": self._health, "draining": self.draining,
            "engine_steps": self.engine_steps,
            "dispatches": self.dispatches,
            "free_blocks": self.free_pages(),
            "pool_utilization": (1.0 - self.free_pages() / total
                                 if total else 0.0),
            # the window layers' lifetime (0 / 0.0 without windowed layers)
            "free_window_blocks": self.free_pages(window=True),
            "window_pool_utilization": self._window_utilization(),
            "window_pages_freed_total": self.window_pages_freed,
            "kv_pages_shared": int((self.refcount > 1).sum()),
            "kv_cow_copies_total": self.cow_copies,
            "spec_proposed_total": self.spec_proposed,
            "spec_accepted_total": self.spec_accepted,
            "slots": [None if s is None else req(s) for s in self._slots],
            "queue": [req(r) for r in self.queue],
            # last KV page-scale drift poll (FLAGS_numerics, quantized
            # pools) — already-fetched host floats, device untouched
            "kv_scales": self._numerics_kv_last,
        }

    def _take_notifications(self) -> List[Request]:
        out, self._notify = self._notify, []
        return out

    def _emit_event(self, event: str, **fields):
        from ..observability import get_event_log
        log = get_event_log()
        if log is not None:
            # role override: serving events stay attributable after
            # merge_event_streams folds them into the trainer timeline
            log.emit(event, role="serving", **fields)

    # -- scheduler -----------------------------------------------------------
    def _blocks_needed(self, r: Request) -> int:
        # total sequence = prompt + NOT-yet-folded generation: a
        # preempted request's prompt already holds its first `folded`
        # emitted tokens, so the request's footprint is invariant across
        # preemptions
        return -(-(len(r.prompt) + r.max_new_tokens - r.folded)
                 // self.bs)

    # -- refcounted pool + prefix cache (ISSUE 17) ---------------------------
    def free_pages(self, window: bool = False) -> int:
        """Reclaimable pages: the free list PLUS cached-free pages
        (refcount 0 but still addressable through the prefix cache until
        evicted for allocation). This is the number pool-leak gates and
        utilization gauges must use — a cached-free page is not leaked.
        ``window=True``: the free pages of the window layers' pool (0
        without one); pages that wait on the step in flight are not free
        yet."""
        if window:
            return len(self.wfree_blocks) if self._windowed else 0
        return len(self.free_blocks) + len(self._cached_free)

    def _window_utilization(self) -> float:
        total = self._num_wblocks - 1
        return (1.0 - len(self.wfree_blocks) / total
                if self._windowed and total else 0.0)

    def _slide_windows(self, q_lens, pos0, lens_after):
        """The window lifetime at the pack of a step: each running row
        gives back the pages wholly behind its window (they wait on the
        step in flight, which may still read them; with none in flight
        they are free at once) and claims the pages its new positions
        enter. Returns the pages given back."""
        from ..enforce import enforce
        bs, nbw, f = self.bs, self._nbw, self._flight
        freed = 0
        for i in np.nonzero(q_lens > 0)[0]:
            lo = max(int(pos0[i]) - (self._window - 1), 0) // bs
            hi = (int(lens_after[i]) - 1) // bs + 1
            gone = range(int(self._wlo[i]), min(lo, int(self._whi[i])))
            for j in gone:
                page = int(self.wtables[i, j % nbw])
                self.wtables[i, j % nbw] = 0
                (self.wfree_blocks if f is None else f.wfree).append(page)
            freed += len(gone)
            self._wlo[i] = max(lo, self._wlo[i])
            first = max(int(self._whi[i]), lo)
            enforce(hi - self._wlo[i] <= self._wreserved[i]
                    and hi - first <= len(self.wfree_blocks),
                    f"slot {i}: the window ring would hold "
                    f"{hi - self._wlo[i]} pages of {self._wreserved[i]} "
                    f"reserved ({len(self.wfree_blocks)} free)",
                    op="ServingEngine")
            for j in range(first, hi):
                self.wtables[i, j % nbw] = self.wfree_blocks.pop()
            self._whi[i] = max(hi, self._whi[i])
        self.window_pages_freed += freed
        return freed

    def _release_window(self, i: int) -> None:
        """A released slot's ring goes back whole (nothing in flight reads
        it: `_release_slot`'s callers have seen to that)."""
        for j in range(int(self._wlo[i]), int(self._whi[i])):
            self.wfree_blocks.append(int(self.wtables[i, j % self._nbw]))
        self.wtables[i, :] = 0
        self._wlo[i] = self._whi[i] = self._wreserved[i] = 0

    def _alloc_blocks(self, n: int) -> List[int]:
        """Allocate n private pages (refcount 1): the free list first,
        then evict least-recently-freed cached pages (their prefix-cache
        entries die with them). Caller checked capacity."""
        out = []
        for _ in range(n):
            if self.free_blocks:
                b = self.free_blocks.pop()
            else:
                b, _ = self._cached_free.popitem(last=False)
                self._drop_cache_entry(b)
                self.cache_evictions += 1
                self._prom.counter_inc(
                    "kv_prefix_evictions_total",
                    help="cached-free prefix pages taken for new pages")
            self.refcount[b] = 1
            out.append(b)
        if self._numerics_kv and out:
            # bump the pages' allocation generation so the numerics
            # scale-drift poll can tell requantization of LIVE pages
            # from free->re-admit churn between two polls
            self._numerics_kv_gen[out] += 1
        return out

    def _drop_cache_entry(self, b: int) -> None:
        h = self._page_hash.pop(b, None)
        if h is not None and self._prefix_cache.get(h) == b:
            del self._prefix_cache[h]

    def _decref(self, b: int) -> None:
        """Drop one holder of page b; at refcount 0 a cache-registered
        page parks in the cached-free LRU (reusable by the next prefix
        hit until evicted), anything else returns to the free list."""
        self.refcount[b] -= 1
        if self.refcount[b] > 0:
            return
        self.refcount[b] = 0
        if self.prefix_share and b in self._page_hash:
            self._cached_free[b] = True
            self._cached_free.move_to_end(b)
        else:
            self.free_blocks.append(b)

    def _chain_of(self, r: Request) -> List[bytes]:
        """Chained hashes of the request's FULL prompt pages:
        h_j = H(h_{j-1} || tokens of page j), so a page hash pins the
        whole prefix up to it — two requests share page j only when
        their first (j+1)*bs prompt tokens are identical."""
        chain = getattr(r, "_chain", None)
        if chain is None:
            import hashlib
            chain = []
            h = b"\x00" * 16
            p = np.asarray(r.prompt, np.int32)
            for j in range(len(p) // self.bs):
                h = hashlib.blake2b(
                    h + p[j * self.bs:(j + 1) * self.bs].tobytes(),
                    digest_size=16).digest()
                chain.append(h)
            r._chain = chain
        return chain

    def _register_pages(self, r: Request) -> None:
        """Register the request's fully-PREFILLED prompt pages in the
        prefix cache (their contents are now canonical for the chain
        hash) and release any fan-out deferral waiting on this owner."""
        if not self.prefix_share or r.slot < 0:
            return
        chain = self._chain_of(r)
        done_pages = min(int(r.prefill_done), len(r.prompt)) // self.bs
        for p in range(min(done_pages, len(chain))):
            h = chain[p]
            b = int(self.tables[r.slot, p])
            if b == 0 or h in self._prefix_cache or b in self._page_hash:
                continue
            self._prefix_cache[h] = b
            self._page_hash[b] = h
        if (chain and r.prefill_done >= len(r.prompt)
                and self._prefix_pending.get(chain[0]) == r.rid):
            del self._prefix_pending[chain[0]]

    def _audit_pool(self) -> None:
        """FLAGS_serving_pool_audit: every live block table must agree
        with the pool refcounts, and free / cached-free / live pages must
        partition the pool exactly — a sharing bug fails HERE, loudly,
        instead of leaking pages silently."""
        if not self.pool_audit:
            return
        expected = np.zeros_like(self.refcount)
        for s in self._slots:
            if s is None:
                continue
            for b in self.tables[s.slot]:
                if b:
                    expected[int(b)] += 1
        if not np.array_equal(expected, self.refcount):
            bad = np.nonzero(expected != self.refcount)[0].tolist()
            raise RuntimeError(
                f"pool refcount audit failed: pages {bad} expected "
                f"{expected[bad].tolist()} vs {self.refcount[bad].tolist()}")
        free = set(self.free_blocks)
        cached = set(self._cached_free)
        live = {int(b) for b in np.nonzero(expected)[0]}
        if (free & cached) or (free & live) or (cached & live):
            raise RuntimeError(
                "pool audit: free/cached-free/live overlap "
                f"{sorted((free & cached) | (free & live) | (cached & live))}")
        if len(free) + len(cached) + len(live) != self._num_blocks - 1:
            raise RuntimeError(
                f"pool audit: {len(free)} free + {len(cached)} cached + "
                f"{len(live)} live != {self._num_blocks - 1} pool pages")
        if self._windowed:
            # free, held in a ring and waiting on the step in flight
            # partition the window pool
            held = [int(self.wtables[i, j % self._nbw])
                    for i in range(self.max_batch)
                    for j in range(int(self._wlo[i]), int(self._whi[i]))]
            waiting = list(self._flight.wfree) if self._flight else []
            pages = self.wfree_blocks + held + waiting
            if (len(set(pages)) != len(pages) or 0 in pages
                    or len(pages) != self._num_wblocks - 1):
                raise RuntimeError(
                    f"window pool audit: {len(self.wfree_blocks)} free + "
                    f"{len(held)} held + {len(waiting)} waiting != "
                    f"{self._num_wblocks - 1} pages, or a page twice")

    def _admit(self) -> List[int]:
        """Admit queued requests into free slots while the pool has
        pages; returns the freshly-admitted slot ids (the step resets
        those slots' page scales in-program). When free pages run
        out the head of the queue WAITS (no starvation) — unless
        ``preempt`` lets it evict a decode victim; a request that could
        never fit even in an empty pool is rejected PER-REQUEST
        (status='failed' + serving_reject event naming the binding cap)
        while its siblings keep admitting. With any deadline present the
        queue admits earliest-deadline-first (stable: no-deadline
        requests keep FIFO order among themselves)."""
        fresh: List[int] = []
        usable = self._num_blocks - 1  # block 0 is reserved scratch
        # why the head still waits when this returns (ADMIT_BLOCKED); the
        # loop below ends on a `break` that says so, or on an empty queue
        self._blocked = (ADMIT_BLOCKED.draining
                         if self.draining and self.queue
                         else ADMIT_BLOCKED.none)
        if self.draining or not self.queue:
            return fresh
        if any(r.deadline is not None for r in self.queue):
            big = float("inf")
            self.queue.sort(key=lambda r: (r.deadline if r.deadline
                                           is not None else big))
        while self.queue:
            try:
                i = self._slots.index(None)
            except ValueError:
                self._blocked = ADMIT_BLOCKED.slot
                break
            r = self.queue[0]
            need = self._blocks_needed(r)
            # the most window pages the row's ring ever holds: reserved
            wneed = min(self._nbw, need) if self._windowed else 0
            if (need > self.tables.shape[1] or need > usable
                    or (self._windowed
                        and wneed > self._num_wblocks - 1)):
                # can never fit, even in an empty pool: reject THIS
                # request and keep admitting — raising here aborted the
                # whole engine step and stranded every sibling
                self.queue.pop(0)
                cap = (f"max_blocks_per_seq {self.tables.shape[1]}"
                       if need > self.tables.shape[1]
                       else f"pool capacity {usable}" if need > usable
                       else f"window pool capacity {self._num_wblocks - 1}")
                r.done = True
                r.status = "failed"
                r.error = (f"needs {need} blocks > {cap} — can never be "
                           "admitted")
                self._prom.counter_inc(
                    "requests_rejected_total",
                    help="requests that could never fit (failed at "
                         "admission)")
                self._emit_event("serving_reject", rid=r.rid,
                                 blocks_needed=need, binding_cap=cap)
                self._notify.append(r)
                continue
            # -- prefix sharing: claim the longest hash-chain match of
            #    already-computed pages BEFORE counting fresh pages
            shared: List[int] = []
            if self.prefix_share:
                chain = self._chain_of(r)
                if chain and chain[0] in self._prefix_pending:
                    # fan-out deferral: an identical prefix is being
                    # prefilled RIGHT NOW by a live owner — admitting
                    # this sibling would recompute the pages it is about
                    # to be able to share; wait (entry clears when the
                    # owner's prefill completes or its slot releases)
                    self._blocked = ADMIT_BLOCKED.prefix
                    break
                for h in chain:
                    b = self._prefix_cache.get(h)
                    if b is None:
                        break
                    if self.refcount[b] == 0:
                        self._cached_free.pop(b, None)
                    self.refcount[b] += 1
                    shared.append(int(b))
            matched = len(shared)
            S = len(r.prompt)
            start = matched * self.bs
            cow = False
            if shared and start >= S:
                # FULL prompt cached: recompute exactly one position
                # (S-1) so this admission still samples a first token —
                # that write lands INSIDE the last shared page, so with
                # any other holder it copy-on-writes instead
                start = S - 1
                cow = self.refcount[shared[-1]] >= 2
            need_new = need - matched + (1 if cow else 0)
            # both pools count: the full pages now, the window pages as a
            # reservation against the pool's size
            if need_new > self.free_pages() or (wneed and wneed > (
                    self._num_wblocks - 1 - int(self._wreserved.sum()))):
                # pool exhaustion: the injected-fault site the resilience
                # tests arm, then either preempt a decode victim or wait.
                # Hand back this attempt's claims first (cached pages
                # return to the reusable cached-free LRU, live shared
                # pages just drop one reference).
                for b in reversed(shared):
                    self._decref(b)
                _faults().maybe_fail("serving/pool_exhausted")
                self._hol_wait_steps += 1
                if self._try_preempt(r, need_new, wneed):
                    continue  # retry the head against the freed pages
                self._blocked = ADMIT_BLOCKED.pages
                break  # head-of-line waits for finishes (no starvation)
            self.queue.pop(0)
            self._hol_wait_steps = 0
            now = time.perf_counter()
            if r.admit_time is None:
                r.admit_time = now
            self._emit_event("serving_admit", rid=r.rid, slot=i,
                             queue_s=now - r.submit_time,
                             preemptions=r.preemptions)
            blocks = self._alloc_blocks(need_new)
            pages = list(shared)
            if cow:
                src = pages[-1]
                dst = blocks.pop(0)
                pages[-1] = dst
                self._cow_pairs.append((src, dst))
                self._decref(src)
                self.cow_copies += 1
            pages.extend(blocks)
            self.tables[i, :] = 0
            self.tables[i, :need] = pages
            # scale-reset mask: inherited (shared non-COW) entries are
            # zeroed so the in-program fresh-row reset cannot wipe the
            # canonical pages' quantization scales (a COW destination
            # stays listed — reset, then scale-copied from its source)
            n_inherit = matched - (1 if cow else 0)
            self._reset_tables[i, :] = 0
            self._reset_tables[i, :need] = pages
            self._reset_tables[i, :n_inherit] = 0
            self._lens[i] = start
            if self._windowed:
                self._wreserved[i] = wneed
            r.slot = i
            r.prefill_done = start
            r.prefix_hit_tokens = start if matched else 0
            self.prefix_hit_tokens += r.prefix_hit_tokens
            self._slots[i] = r
            fresh.append(i)
            if self.prefix_share:
                chain = self._chain_of(r)
                if chain and chain[0] not in self._prefix_cache:
                    # brand-new prefix: later identical prompts defer
                    # until this owner's pages are registered
                    self._prefix_pending[chain[0]] = r.rid
                if matched:
                    self._prom.counter_inc(
                        "kv_prefix_hits_total",
                        help="admissions that reused cached prefix pages")
        return fresh

    def _try_preempt(self, head: Request, need: int, wneed: int = 0) -> bool:
        """Preempt-and-requeue (ISSUE 13c): evict a decode-phase victim so
        the pool-blocked queue head can make progress — its pages free,
        and the victim re-enqueues with prompt+generated-prefix for
        recompute (greedy replay is token-identical). Victim choice:
        prefer requests without deadlines, then latest deadline, then most
        remaining work. Fires only after the head has been blocked
        ``preempt_wait_steps`` consecutive admission attempts, and never
        preempts a victim that would not actually unblock the head or one
        already preempted 3 times (anti-thrash)."""
        if not self.preempt:
            return False
        if self._hol_wait_steps < self.preempt_wait_steps:
            return False
        if self._flight is not None:
            # a victim is chosen, and its output folded into its prompt,
            # from settled state; what the step in flight frees may
            # already let the head in
            self.settle("preempt")
            self._hol_wait_steps -= 1   # the retry counts this wait again
            return True
        big = float("inf")
        victims = [r for r in self._slots
                   if r is not None and r.prefill_done >= len(r.prompt)
                   and r.preemptions < 3]
        # urgency: with deadlines, only preempt a victim LESS urgent than
        # the head; without deadlines any decode victim unblocks the line
        if head.deadline is not None:
            victims = [r for r in victims
                       if (r.deadline or big) > head.deadline]
        victims.sort(key=lambda r: (r.deadline is not None,
                                    -(r.deadline or big) if r.deadline
                                    else 0.0,
                                    -(r.max_new_tokens - len(r.output))))
        for v in victims:
            # only SOLE-holder pages actually return to the pool when
            # this victim releases — evicting a request whose pages are
            # mostly shared frees almost nothing
            held = sum(1 for b in self.tables[v.slot]
                       if b != 0 and self.refcount[int(b)] == 1)
            wroom = (self._num_wblocks - 1 - int(self._wreserved.sum())
                     + int(self._wreserved[v.slot])) if wneed else 0
            if need <= self.free_pages() + held and wneed <= wroom:
                self._preempt(v)
                return True
        return False

    def _preempt(self, r: Request):
        """Evict a running decode request: free its pages and re-enqueue
        it with its emitted tokens folded into the prompt, so re-admission
        re-prefills prompt+prefix and decoding continues where it left
        off (`output` keeps the emitted tokens — remaining budget and the
        finish condition are unchanged)."""
        slot = r.slot
        self._release_slot(r)
        fresh = r.output[r.folded:]  # only tokens NOT already folded by
        #                              an earlier preemption
        if fresh:
            r.prompt = np.concatenate(
                [r.prompt, np.asarray(fresh, np.int32)])
            r._chain = None  # prompt changed: hash chain is stale
        r.folded = len(r.output)
        r.prefill_done = 0
        r.preemptions += 1
        self.preempted += 1
        self.queue.append(r)
        self._prom.counter_inc("requests_preempted_total",
                               help="decode victims evicted-and-requeued "
                                    "under pool exhaustion")
        self._emit_event("serving_preempt", rid=r.rid, slot=slot,
                         emitted=len(r.output),
                         preemptions=r.preemptions)

    def _release_slot(self, r: Request):
        """Return a running request's pages + slot to the pool (shared by
        finish/cancel/preempt). Pages DECREF rather than free: a page
        another block table still references stays live, and a
        cache-registered page parks in the cached-free LRU for the next
        prefix hit. Flags-off this is the old free-list append, in the
        same sorted order."""
        i = r.slot
        used = {int(b) for b in self.tables[i] if b != 0}
        for b in sorted(used):
            self._decref(b)
        self.tables[i, :] = 0
        self._reset_tables[i, :] = 0
        if self._windowed:
            self._release_window(i)
        self._lens[i] = 0
        self._slots[i] = None
        self._pending_tok[i] = 0
        r.slot = -1
        if self._prefix_pending:
            for h in [h for h, rid in self._prefix_pending.items()
                      if rid == r.rid]:
                del self._prefix_pending[h]
        self._audit_pool()

    def _finish(self, r: Request):
        """A request ended in the step just walked. One that the host
        could not see ending (an EOS, a raising callback) rides the step
        already in flight: that step writes into its pages, so slot and
        pages are released when IT has been walked (`_walk_ragged`)."""
        r.done = True
        f = self._flight
        if f is None or f.q_lens[r.slot] == 0:
            self._release_slot(r)

    def _shed(self, r: Request, reason: str):
        """Drop a queued request. status='shed' means it NEVER delivered
        anything; a preempted-and-requeued victim that already emitted
        tokens reports 'cancelled' instead (partial output kept) — a
        consumer resubmitting a 'shed' request verbatim must never
        double-deliver a prefix."""
        r.done = True
        r.error = reason
        if r.output:
            self._mark_cancelled(r, reason)
            return
        r.status = "shed"
        self._prom.counter_inc("requests_shed_total",
                               help="requests shed before running "
                                    "(deadline/queue_full/overload/"
                                    "draining)")
        self._emit_event("serving_shed", rid=r.rid, reason=reason,
                         queue_depth=len(self.queue))

    def _mark_cancelled(self, r: Request, reason: str):
        """The ONE copy of cancellation bookkeeping (shared by _cancel
        and _shed's delivered-prefix branch)."""
        r.done = True
        r.status = "cancelled"
        r.error = reason
        self._prom.counter_inc("requests_cancelled_total",
                               help="requests cancelled after delivering "
                                    "tokens (deadline expiry / drain "
                                    "endgame / dropped requeued victim)")
        self._emit_event("serving_cancelled", rid=r.rid, reason=reason,
                         emitted=len(r.output))

    def _cancel(self, r: Request, reason: str):
        """Cancel an IN-FLIGHT request mid-generation: pages freed and
        accounted, partial output kept, status='cancelled'."""
        self._release_slot(r)
        self._mark_cancelled(r, reason)

    def _expire(self) -> List[Request]:
        """Deadline enforcement, both ends: shed stale QUEUED requests and
        cancel expired IN-FLIGHT ones (their pages free before this
        step's admission runs). No-deadline requests cost one comparison
        each — behavior is untouched."""
        if (not self.queue or all(r.deadline is None for r in self.queue)) \
                and all(s is None or s.deadline is None
                        for s in self._slots):
            return []
        now = time.perf_counter()
        if any(r is not None and r.deadline is not None and now > r.deadline
               for r in self._slots):
            self.settle("expire")   # cancel from settled state
        out: List[Request] = []
        keep: List[Request] = []
        for r in self.queue:
            if r.deadline is not None and now > r.deadline:
                self._shed(r, "deadline")
                out.append(r)
            else:
                keep.append(r)
        self.queue = keep
        for r in list(self._slots):
            if (r is not None and r.deadline is not None
                    and now > r.deadline):
                self._cancel(r, "deadline")
                out.append(r)
        return out

    def _shed_overload(self) -> List[Request]:
        """SLO-driven load shedding (ISSUE 13b): when the prom TTFT
        recent-window p95 crosses ``shed_headroom`` of ``ttft_slo_s``
        the engine is not keeping up — trim the queue to what the slots
        can absorb in about one wave (``max_batch``), keeping the NEWEST
        arrivals: the aged head has already burned most of its latency
        budget (with deadlines, ``_expire`` would shortly shed it
        anyway), so admitting fresh requests is what keeps ADMITTED p99
        inside the SLO instead of every request missing it. The headroom
        factor (default 0.5) triggers BEFORE the first violation —
        TTFT moves in whole engine-step quanta, so a policy that waits
        for p95 > SLO has already admitted violators by the time it
        reacts. Hysteresis: trim only once the queue exceeds TWICE the
        slot horizon — the 16-sample window's p95 (its max) is sticky, so
        trimming on every step while it decays would shed far past the
        overload fraction (at 2x load the ideal is to shed half the
        arrivals)."""
        if (not self.shed_on_overload or self.ttft_slo_s is None
                or len(self.queue) <= 2 * self.max_batch):
            return []
        p95 = self._prom.quantile("ttft_seconds", 0.95)
        if p95 is None or p95 <= self.shed_headroom * self.ttft_slo_s:
            return []
        if any(r.deadline is not None for r in self.queue):
            # _admit's in-place EDF sort persists in the queue, so
            # "newest arrivals" is not the tail here — with deadlines the
            # most-urgent (earliest-deadline) requests are the ones worth
            # keeping, consistent with EDF admission
            big = float("inf")
            self.queue.sort(key=lambda r: (r.deadline if r.deadline
                                           is not None else big))
            shed, self.queue = (self.queue[self.max_batch:],
                                self.queue[:self.max_batch])
        else:
            shed, self.queue = (self.queue[:-self.max_batch],
                                self.queue[-self.max_batch:])
        for r in shed:
            self._shed(r, "overload")
        self._prom.gauge_set("queue_depth", len(self.queue))
        return shed

    def _emit(self, r: Request, tok: int) -> bool:
        """Record a sampled token; True if the request just finished. A
        raising user ``on_token`` callback fails ONLY this request
        (status='failed', serving_callback_error event) — it must never
        kill the engine step and strand every co-scheduled sibling."""
        r.output.append(tok)
        self._tokens_total += 1
        if len(r.output) == 1:
            self._note_first_token(r)
        if r.on_token is not None:
            try:
                r.on_token(r.rid, tok)
            except Exception as e:
                r.status = "failed"
                r.error = f"on_token callback raised: {e!r}"
                r.on_token = None
                self._prom.counter_inc(
                    "callback_errors_total",
                    help="requests failed by a raising on_token callback")
                self._emit_event("serving_callback_error", rid=r.rid,
                                 error=repr(e), emitted=len(r.output))
                return True  # finish (and free) the poisoned request
        return (len(r.output) >= r.max_new_tokens
                or (r.eos_id is not None and tok == r.eos_id))

    def _note_first_token(self, r: Request):
        """The first token's hand-over closes the request's record: TTFT
        to prom, and (inside `serving_walk`) the four phases to the
        collector, each with its own start and end, and the instant span
        whose attributes carry them into a profiler session. The
        microseconds are differences of the marks' rounded offsets from
        submission, so they sum to the TTFT's own."""
        r.first_token_time = now = time.perf_counter()
        r.ttft_s = now - r.submit_time
        self._prom.summary_observe(
            "ttft_seconds", r.ttft_s,
            help="submit-to-first-token latency",
            window=self._ttft_window)
        self._prom.histogram_observe(
            "ttft_seconds_hist", r.ttft_s,
            help="submit-to-first-token latency distribution")
        marks = r.marks()
        for name, a, b in zip(REQUEST_PHASES, marks, marks[1:]):
            record_interval(name, a, b, rid=r.rid)
        at = [round((t - r.submit_time) * 1e6) for t in marks]
        with RecordEvent(REQUEST_SPANS.first_token, **dict(zip(
                FIRST_TOKEN_ATTRS,
                (r.rid, len(r.prompt), *(b - a for a, b in zip(at, at[1:])),
                 r.prefill_steps, r.starved_steps,
                 self.engine_steps - r.prefill_end_step, r.preemptions)))):
            pass

    def step(self) -> List[Request]:
        """One engine iteration, with ONE STEP IN FLIGHT: admit, pack,
        upload and dispatch step n+1 (ONE compiled program: prefill
        chunks + decode burst fused over a packed ragged batch) FIRST,
        then fetch and walk step n, which the device finished while the
        host packed. Returns every request that reached a TERMINAL state
        in what this call walked — finished, plus deadline-shed/
        cancelled, overload-shed, rejected, and sheds or settles queued
        since the last step (check ``Request.status``). After the call
        step n+1 is still running: `Request.output`, `prefill_done` and
        `snapshot()` show COMMITTED (walked) progress; `settle()` brings
        them up to the dispatched step, and so does any outside read of
        `slots`, `lens` or a device-state view (the pools, their scales,
        `ssm_state`, `conv_tail`). `snapshot()`, `load_stats()`, `prom`
        and the counters never settle and never touch the device. Where
        the next pack depends on the result of the step in flight the
        engine settles first, by what it sees in its own input (see
        `settle`): the same loop, the barrier before the pack instead of
        after the dispatch.

        The whole step runs inside a ``serving_step`` RecordEvent span,
        and the host work inside it is covered, without holes, by the
        child spans of ``observability.trace.SERVING_SPANS`` (sweep,
        admission, pack, upload, unified dispatch of step n+1, then
        fetch and walk of step n, metrics; `serving_fetch` is the host
        blocked on the device). Every span is a
        ``jax.profiler.TraceAnnotation`` too, so serving lands on the SAME
        host timeline as training — Profiler summaries, chrome-trace
        exports, observability.capture_spans — and on the device trace's
        clock in any ``jax.profiler`` session. The ``serving/step``
        fault-injection site fires FIRST — a kill/hang clause takes the
        whole step down exactly as a wedged device would."""
        self.engine_steps += 1
        _faults().maybe_fail("serving/step")
        with RecordEvent(SERVING_SPANS.step):
            with RecordEvent(SERVING_SPANS.sweep):
                terminal = self._take_notifications()
                terminal += self._expire()
                terminal += self._shed_overload()
            out = self._step_ragged()
            if self._health == "loading":
                self._health = "ready"
            # admission-time rejections and what a settle walked land in
            # _notify DURING the step body — drain them now so a run that
            # ends this step still reports them
            return terminal + out + self._take_notifications()

    def _numerics_kv_poll(self) -> None:
        """KV-pool page-scale drift telemetry (FLAGS_numerics, quantized
        pools): every telemetry interval, fetch the per-(head, page)
        scales and export gauges — ``kv_scale_max`` / ``kv_scale_mean``
        over live pages, ``kv_pages_live`` and ``kv_scale_regrew_frac``
        (fraction of live pages whose scale GREW since the last poll —
        each growth requantized that page's existing tokens onto a
        coarser grid) — plus one role-tagged ``numerics_kv`` JSONL
        event. Host-side read of device state; never changes the
        compiled program."""
        if (not self._numerics_kv
                or self.engine_steps % self._numerics_kv_interval):
            return
        import jax
        ks, vs = jax.device_get((self._k_scales, self._v_scales))
        cur = np.maximum(np.max(np.asarray(ks, np.float32), axis=0),
                         np.max(np.asarray(vs, np.float32), axis=0))
        # page axis is last ([L/H, ..., NB] — reduce everything else)
        cur = cur.reshape(-1, cur.shape[-1]).max(axis=0)   # [NB]
        # liveness from the HOST pool accounting, not scale > 0: freed
        # pages keep their stale scale until re-admission zeroes it
        # (reset_page_scales runs at re-admit), so scale alone would
        # count dead pages and read allocation churn as drift
        alloc = np.ones(cur.shape[0], bool)
        alloc[0] = False  # reserved scratch block
        if self.free_blocks:
            alloc[np.asarray(self.free_blocks, np.int64)] = False
        if self._cached_free:
            # cached-free prefix pages are reclaimable, not live — their
            # scales are frozen until eviction or the next prefix hit
            alloc[np.fromiter(self._cached_free, np.int64)] = False
        live = alloc & (cur > 0.0)  # allocated AND written
        n_live = int(live.sum())
        prev = self._numerics_kv_prev
        grew = 0.0
        if prev is not None:
            # requantization drift: pages live at BOTH polls with the
            # SAME allocation generation (a page freed + re-admitted in
            # between regrew its scale from the reset, not from
            # re-rounding existing tokens) whose scale grew
            both = (live & prev["live"]
                    & (self._numerics_kv_gen == prev["gen"]))
            if both.any():
                grew = float(np.sum(both & (cur > prev["page_max"]
                                            + 1e-12)) / both.sum())
        stats = {
            "kv_scale_max": float(cur[live].max()) if n_live else 0.0,
            "kv_scale_mean": float(cur[live].mean()) if n_live else 0.0,
            "kv_pages_live": float(n_live),
            "kv_scale_regrew_frac": grew,
        }
        for name, v in stats.items():
            self._prom.gauge_set(name, v,
                                 help="numerics: KV-pool page-scale "
                                      "drift (FLAGS_numerics)")
        self._numerics_kv_prev = {"page_max": cur, "live": live,
                                  "gen": self._numerics_kv_gen.copy()}
        self._numerics_kv_last = stats
        self._emit_event("numerics_kv", step=self.engine_steps, **stats)

    def _check_tok(self, r: Request, tok: int) -> int:
        """Sampled-token sanity gate: an out-of-range token means the
        sampling path is poisoned (nonfinite logits, corrupted pool) —
        raise with the rid attached so the resilient driver's circuit
        breaker fails THAT request instead of retrying the engine
        forever. Two comparisons per token; valid tokens untouched."""
        if tok < 0 or tok >= self.cfg.vocab_size:
            raise NonFiniteSampleError(r.rid, tok)
        return tok

    def _step_ragged(self) -> List[Request]:
        """The single-dispatch step: admit, pack ONE ragged token batch
        (decode rows first — one token each, always granted — then
        prefill chunks sharing the leftover token budget) from what is
        committed plus what is in flight, run the ONE unified program
        (K-token decode burst fused in), THEN fetch the step before and
        walk its [K, R] token matrix on the host. One compiled dispatch,
        one fetch. Each phase is a child span of ``serving_step``
        (SERVING_SPANS)."""
        t_step0 = time.perf_counter()
        if self._t_first_step is None:
            self._t_first_step = t_step0
        tokens_before = self._tokens_total
        if self.spec_k > 0:
            self.settle("spec")     # the proposer reads Request.output
        if self._windowed and self._flight is not None:
            # the most window pages this step's rows can claim: a page a
            # `bs` positions entered, and a row's ring never passes its
            # reservation, so only pages that wait on the step in flight
            # can be missing
            dec, pre, _, _ = self._schedulable()
            grow = self.chunk + self.decode_burst - 1
            if len(self.wfree_blocks) < (
                    len(dec) * -(-self.decode_burst // self.bs)
                    + len(pre) * -(-grow // self.bs)):
                self.settle("window")
        with RecordEvent(SERVING_SPANS.admission) as admission:
            preempted = self.preempted
            fresh_slots = self._admit()
            self._note_pool_peak()
            admission.set(**dict(zip(ADMISSION_ATTRS, (
                len(fresh_slots), len(self.queue), self._blocked,
                self.preempted - preempted))))
        b = self._pack_ragged(fresh_slots)
        if b is None:
            self.settle("tail")     # a no-op with nothing in flight
            self._step_metrics(t_step0, tokens_before, 0, 0)
            return []
        args = self._upload_ragged(b)
        self.decode_microsteps += b.K
        self.dispatches += 1
        prev = self._flight
        if prev is not None:
            self._prom.counter_inc("steps_overlapped_total")
        now = time.perf_counter()
        for r in b.ending:      # the prompt's last chunk goes out
            if r.prefill_end_time is None:
                r.prefill_end_time, r.prefill_end_step = (now,
                                                          self.engine_steps)
        with RecordEvent(SERVING_SPANS.dispatch, **dict(zip(DISPATCH_ATTRS, (
                self.engine_steps, b.K, len(b.dec), len(b.pre), b.q_tokens,
                b.kv_tokens, b.attn_pages, b.kv_tiles, int(prev is not None),
                len(b.pre) - len(b.grants), sum(b.grants.values()),
                self.token_budget))), **b.model_attrs), self._first_call(b):
            _faults().maybe_fail("serving/dispatch")
            out = self._unified(b.K, spec=b.use_spec)(*args)
        if _startup.RECOMPILES and _startup.take_recompiles():
            # a variant that had run before was compiled again
            self._note_compiled(b.K, b.use_spec, now, recompile=1)
        route = ()
        if self.model.routed:   # ids0, ids_burst, stats: fetched with toks
            *out, ids0, ids_burst, stats = out
            route = (ids0, ids_burst, stats)
        if self._windowed:
            *out, self._wk_pools, self._wv_pools = out
        if self.model.recurrent:
            *out, self._ssm_state, self._conv_tail = out
        toks, *out = out
        greedy_all = out.pop(0) if b.use_spec else None
        (self._k_pools, self._v_pools, self._k_scales, self._v_scales,
         lens, self._last_tok) = out
        b.out = (toks, greedy_all, lens) + route
        self._flight = b
        finished = [] if prev is None else self._land(prev)
        if not self.queue and not any(self._schedulable()[:2]):
            # the step just dispatched is the last one the host can
            # schedule: nothing to pack behind it, so its result is not
            # held back a call
            self.settle("tail")
        self._step_metrics(t_step0, tokens_before, len(b.pre), len(b.dec))
        return finished

    def _first_call(self, b):
        """Around a dispatch: the `startup_program_first_call` span where
        the step's variant has never been built (its first call traces,
        lowers and compiles or loads it), else nothing."""
        if (b.K, b.use_spec) in self._unified_cache:
            return _startup.NO_SPAN
        return _startup.FirstCall(self._note_compiled, b.K, b.use_spec)

    def _note_compiled(self, k, spec, since, recompile=0):
        """The operator's view of the compile events this thread ended
        since `since`: the two prom series and one `program_compiled`
        line. Written where a program was built, never by a step that
        built none."""
        done = _startup.compiled_since(since)
        seconds = 0.0
        for stage in ("trace", "lower", "backend"):
            seconds += done[stage + "_us"] / 1e6
            self._prom.counter_inc(
                "compile_seconds_total", done[stage + "_us"] / 1e6,
                labels={"stage": stage},
                help="seconds the serving step's programs took to trace, "
                     "lower, and compile or load from the persistent cache")
        for result, n in done["results"].items():
            self._prom.counter_inc(
                "compile_cache_total", n, labels={"result": result},
                help="backend compilations of the serving step's programs "
                     "by what the persistent cache did")
        self._emit_event("program_compiled", fun=done["fun"], k=k,
                         spec=int(spec), seconds=round(seconds, 6),
                         cache=done["cache"], recompile=recompile)
        return done

    def _land(self, f) -> List[Request]:
        """Fetch a dispatched step's tokens and walk them; returns the
        requests that finished in it."""
        with RecordEvent(SERVING_SPANS.fetch) as fetch:
            # ONE host fetch: the copies start together, then the host
            # waits (a fetch of its own for `lens` cost 0.4 ms a step)
            toks, greedy_all, lens, *route = jax.device_get(f.out)
            if route:   # the span closes with the counts of the step it
                fetch.set(**self._note_routing(route[2]))   # landed
        return self._walk_ragged(f, toks, greedy_all, lens, *route[:2])

    def _note_routing(self, stats):
        """A landed step's router counts, stats [K, L, columns] of its K
        passes and L layers, the columns named by the model's
        `route_stats`: added to the totals, and returned as the fetch
        span's MOE_FETCH_ATTRS (sums, but the largest load and the tile
        height: the tallest of the step's passes)."""
        col = {name: stats[..., i]
               for i, name in enumerate(self.model.route_stats)}
        touched, assigned = (int(col["touched"].sum()),
                             int(col["assignments"].sum()))
        self.moe_experts_touched += touched
        self.moe_assignments += assigned
        ran = col["assignments"] > 0
        self.moe_passes += int(ran.any(axis=1).sum())
        self._moe_load += np.where(
            ran, col["load_max"] * self._moe_held
            / np.maximum(col["assignments"], 1), 0.0).sum(axis=0)
        attrs = dict(zip(MOE_FETCH_ATTRS, (
            touched, assigned, int(col["load_max"].max()),
            int(col["tiles"].sum()), int(col["tile_rows"].max()))))
        if "local_tokens" in col:   # a group-limited router's two more
            local, routed = (int(col["local_tokens"].sum()),
                             int(col["tokens"].sum()))
            self.moe_local_tokens += local
            self.moe_tokens += routed
            attrs.update(zip(MOE_LOCAL_FETCH_ATTRS, (local, routed)))
        return attrs

    @staticmethod
    def _advance(q_lens, pos0, sample0, remaining, K):
        """Where a packed step leaves every row if no EOS falls: the ONE
        place that knows how far a step moves a row (one token a pass).
        A sampling row emits ``min(K, remaining)`` tokens — pass 1's
        (whatever `remaining` says), and one a burst pass while it may
        still emit — and its context grows
        by its q_len and by one position a burst pass it is alive for.
        The pack of the NEXT step schedules from this while the step is
        in flight, the dispatch attributes count the KV positions from
        it, and the walk holds the device's `lens` to it.
        Returns (emit [R], lens_after [R])."""
        emit = np.where(sample0, np.clip(remaining, 1, K), 0).astype(np.int32)
        return emit, (pos0 + q_lens + np.maximum(emit - 1, 0)).astype(
            np.int32)

    def _schedulable(self):
        """The rows the next step can run, from what is committed plus
        where the step in flight will leave them (`_advance`): decode
        rows, prefilling rows, and per slot the prefill cursor and the
        tokens emitted by then. A row that step finishes by count is
        not among them, nor one that had ended before it was
        dispatched (an EOS row on its wasted step)."""
        f = self._flight
        dec, pre, done_pre, emitted = [], [], {}, {}
        for r in self._slots:
            if r is None or r.done:
                continue
            i = r.slot
            flying = int(f.emit[i]) if f is not None else 0
            emitted[i] = len(r.output) + flying
            if flying and emitted[i] >= r.max_new_tokens:
                continue
            done_pre[i] = r.prefill_done + (f.grants.get(i, 0)
                                            if f is not None else 0)
            (dec if done_pre[i] >= len(r.prompt) else pre).append(r)
        return dec, pre, done_pre, emitted

    @RecordEvent(SERVING_SPANS.pack)
    def _pack_ragged(self, fresh_slots):
        """The step's packed host arrays, burst size and row lists, or
        None when no slot has work. A row is packed from where the step
        in flight will leave it (`_advance`): its prefill cursor plus the
        granted chunk, its tokens emitted, its context; a row that step
        finishes by count is left out, and a decode row whose input token
        that step is sampling gets the sentinel -1, which the program
        fills from the token it kept (`unified_step`'s ``prev_tok``)."""
        R, T = self.max_batch, self.token_budget
        f = self._flight
        dec, pre, done_pre, emitted = self._schedulable()
        if not dec and not pre:
            return None
        lens = self._lens
        if f is not None:
            lens = np.where(f.q_lens > 0, f.lens_after, lens)

        tokens = np.zeros((T,), np.int32)
        row_of = np.zeros((T,), np.int32)
        off_of = np.full((T,), T, np.int32)  # > any q_len -> padding
        starts = np.zeros((R,), np.int32)
        pos0 = np.zeros((R,), np.int32)
        q_lens = np.zeros((R,), np.int32)
        fresh = np.zeros((R,), bool)
        sample0 = np.zeros((R,), bool)
        remaining = np.zeros((R,), np.int32)
        eos_ids = np.full((R,), -1, np.int32)
        temps = np.zeros((R,), np.float32)
        for i in fresh_slots:
            fresh[i] = True
        cursor = 0
        props_by_slot: Dict[int, List[int]] = {}
        for idx, r in enumerate(dec):  # decode rows: always granted
            i = r.slot
            props: List[int] = []
            if self.spec_k > 0 and r.temperature == 0:
                # speculative drafts ride the SAME dispatch as q_len =
                # 1 + k verify rows; cap: the proposer's k, the row's
                # pre-allocated footprint (k <= remaining - 1 keeps
                # every draft's KV write inside it), and the token
                # budget after every later decode row's guaranteed 1
                # (settled: `step` settles before a speculative pack)
                room = T - cursor - (len(dec) - idx - 1) - 1
                cap = min(self.spec_k,
                          r.max_new_tokens - len(r.output) - 1, room)
                if cap > 0:
                    ctx = np.concatenate(
                        [np.asarray(r.prompt, np.int64),
                         np.asarray(r.output[r.folded:], np.int64)])
                    for t in self._proposer(ctx, cap)[:cap]:
                        if not 0 <= int(t) < self.cfg.vocab_size:
                            break  # defensive: never embed out-of-vocab
                        props.append(int(t))
            q_lens[i] = 1 + len(props)
            pos0[i] = lens[i]
            sample0[i] = True
            remaining[i] = r.max_new_tokens - emitted[i]
            if r.eos_id is not None:
                eos_ids[i] = r.eos_id
            temps[i] = r.temperature
            # its input token: known once walked, else still on the device
            known = f is None or f.emit[i] == 0
            row_toks = [self._pending_tok[i] if known else -1] + props
            tokens[cursor:cursor + len(row_toks)] = row_toks
            row_of[cursor:cursor + len(row_toks)] = i
            off_of[cursor:cursor + len(row_toks)] = np.arange(len(row_toks))
            starts[i] = cursor
            cursor += len(row_toks)
            if props:
                props_by_slot[i] = props
        use_spec = bool(props_by_slot)
        grants: Dict[int, int] = {}
        ending: List[Request] = []
        now = time.perf_counter()
        for r in pre:  # prefill chunks share the leftover budget
            i = r.slot
            lo = done_pre[i]
            pos0[i] = lo  # keeps device lens honest even at zero grant
            todo = len(r.prompt) - lo
            grant = min(self.chunk, todo, T - cursor)
            if grant <= 0:
                r.starved_steps += 1
                continue
            r.prefill_steps += 1
            if r.first_grant_time is None:
                r.first_grant_time = now
            grants[i] = grant
            q_lens[i] = grant
            completing = lo + grant >= len(r.prompt)
            if completing:
                ending.append(r)
            sample0[i] = completing
            # remaining-to-EMIT: a preempted-and-requeued request's
            # emitted prefix lives in both prompt and output
            remaining[i] = (r.max_new_tokens - emitted[i]
                            if completing else 0)
            if r.eos_id is not None:
                eos_ids[i] = r.eos_id
            temps[i] = r.temperature
            tokens[cursor:cursor + grant] = r.prompt[lo:lo + grant]
            row_of[cursor:cursor + grant] = i
            off_of[cursor:cursor + grant] = np.arange(grant)
            starts[i] = cursor
            cursor += grant

        if use_spec:
            # the verify pass subsumes the burst: up to k+1 tokens per
            # row already ride pass 1, and the micro-scan cannot extend
            # a row whose acceptance point is only known on the host
            K = 1
        else:
            K = self._pick_burst(len(pre))
            if not sample0.any():
                # every slot is mid-prefill: no row can sample this
                # step, so the K-1 decode micro-steps would run full
                # forward passes over all-zero q_lens. K=1 is an
                # already-compiled size.
                K = 1
        emit, lens_after = self._advance(q_lens, pos0, sample0, remaining, K)
        # KV positions the step attends: each row of pass 1 its whole
        # context, then each sampling row one position more per burst
        # pass while it may still emit (an EOS inside the burst stops a
        # row earlier; the host learns that only from the fetch). The
        # pages those positions fill are the (row, page) pairs the
        # attention kernel walks: of a pass's R x nb table slots, the
        # ones that carry work
        ran = q_lens > 0
        kv_end = (pos0 + q_lens).astype(np.int64)
        kv_tokens = int(kv_end[ran].sum())
        attn_pages = int((-(-kv_end[ran] // self.bs)).sum())
        burst_rows = 0      # rows the K-1 burst passes run, summed
        # of `attn_pages`, the pairs that rows of one token on one prefix
        # attend TOGETHER (a latent cache's kernel groups them by itself,
        # from the tables: `mla_attention.decode_groups`)
        shared = (shared_pages(self.tables, q_lens, kv_end, bs=self.bs)
                  if self._latent else 0)
        for j in range(1, K):
            alive = emit > j
            kv_tokens += int((kv_end[alive] + j).sum())
            attn_pages += int((-(-(kv_end[alive] + j) // self.bs)).sum())
            burst_rows += int(alive.sum())
            if self._latent:
                shared += shared_pages(self.tables, alive.astype(np.int32),
                                       kv_end + j, bs=self.bs)
        # the tiles the in-place append walks
        # (`kernels.pallas.kv_append.tile_work`'s n, summed over the
        # passes): a row of pass 1 the tiles its new positions lie in, a
        # row of a burst pass one; a quantized pool has none
        tile, kv_tiles = self._append_tile, 0
        if tile:
            kv_tiles = burst_rows + int(((kv_end[ran] - 1) // tile
                                         - pos0[ran] // tile + 1).sum())
        model_attrs = {}
        wtables = None
        if self._windowed:
            # what a window layer reads of a row in a pass: the positions
            # its window lets the row's queries see, and their pages
            W, bs = self._window, self.bs
            n_full = self.model.kv_layers(self.cfg)
            n_win = self.model.window_layers(self.cfg)
            q, end = q_lens[ran].astype(np.int64), kv_end[ran]
            win_tokens = int(np.minimum(end, W - 1 + q).sum())
            win_pages = int((-(-end // bs)
                             - np.maximum(end - q - (W - 1), 0) // bs).sum())
            for j in range(1, K):
                end = kv_end[emit > j] + j
                win_tokens += int(np.minimum(end, W).sum())
                win_pages += int((-(-end // bs)
                                  - np.maximum(end - W, 0) // bs).sum())
            freed = self._slide_windows(q_lens, pos0, lens_after)
            wtables = self.wtables.copy()
            model_attrs = dict(zip(WINDOW_DISPATCH_ATTRS, (
                n_full * kv_tokens + n_win * win_tokens, win_pages, freed)))
        if self.model.recurrent:
            # a row that starts at position 0 has its state zeroed by the
            # program (admission, and re-prefill after a preemption)
            self.ssm_resets += int((ran & (pos0 == 0)).sum())
            model_attrs = dict(zip(SSM_DISPATCH_ATTRS, (
                int(ran.sum()), burst_rows, cursor + burst_rows)))
        if self._latent:
            # the (query, key) pairs beyond the one a row `kv_tokens`
            # counts: what a chunk against its prefix costs the attention
            q, p = q_lens[ran].astype(np.int64), pos0[ran].astype(np.int64)
            model_attrs = dict(zip(LATENT_DISPATCH_ATTRS, (
                sum(self._slots[i].prefix_hit_tokens for i in fresh_slots),
                int((q * p + q * (q + 1) // 2 - (p + q)).sum()), shared)))
        if not self._latent:    # its attention is another kernel
            chunk = self._chunk_pages(q_lens, kv_end, emit, K)
            model_attrs = {**dict(zip(CHUNK_DISPATCH_ATTRS, chunk.tolist())),
                           **model_attrs}
        return _PackedStep(
            dec=dec, pre=pre, ending=ending, grants=grants,
            props_by_slot=props_by_slot,
            use_spec=use_spec, K=K, q_tokens=cursor, kv_tokens=kv_tokens,
            attn_pages=attn_pages, kv_tiles=kv_tiles,
            starts=starts, pos0=pos0, q_lens=q_lens, emit=emit,
            lens_after=lens_after, model_attrs=model_attrs, wtables=wtables,
            # the tables are the engine's own and change under the step
            # in flight (a walk releases, an admission claims): the
            # program gets a copy
            arrays=(tokens, row_of, off_of, starts, pos0, q_lens,
                    self.tables.copy(), fresh, sample0, remaining, eos_ids,
                    temps))

    @functools.cached_property
    def _wide_arm_pages(self):
        """`wide_arm_pages` at this engine's geometry: what one call of the
        attention kernel walks on its wide arm, and masks there."""
        from ..kernels.pallas.ragged_paged_attention import wide_arm_pages
        _, hkv, _, bs, D = self._k_pools.shape
        tp = self._mesh.shape[self._mp_axis] if self._mesh else 1
        return functools.partial(
            wide_arm_pages, hq=self.cfg.num_heads // tp, hkv=hkv // tp,
            bs=bs, D=D, itemsize=self._k_pools.dtype.itemsize)

    def _chunk_pages(self, q_lens, kv_end, emit, K):
        """[pages, masked pages] of the step's wide-arm rows, over the K
        passes (a burst pass's rows hold one token: the narrow arm's
        unless a query group is wider than a tile) and over the layers, a
        window layer counted under its window."""
        def layer(window=None):
            count = functools.partial(self._wide_arm_pages, window=window)
            ran = q_lens > 0
            return np.sum(
                [count(q_lens[ran], kv_end[ran], c_att=self._c_att)]
                + [count(np.ones(int((emit > j).sum()), np.int64),
                         kv_end[emit > j] + j, c_att=1)
                   for j in range(1, K)], axis=0)
        total = self.model.kv_layers(self.cfg) * layer()
        if self._windowed:
            total += self.model.window_layers(self.cfg) * layer(self._window)
        return total

    @RecordEvent(SERVING_SPANS.upload)
    def _upload_ragged(self, b):
        """The unified program's arguments: the packed arrays on the
        device, the token each slot emitted last (never fetched), this
        step's PRNG key, the pools."""
        self._key, sub = _split_key(self._key)
        # one transfer call for the twelve small arrays, not an asarray
        # each: the host's share of a step is what a short step feels
        if self._windowed:
            # unified_step's full argument list: no scales, no
            # copy-on-write, no recurrent state, then the second lifetime
            *arrays, wtables = jax.device_put(list(b.arrays) + [b.wtables])
            return ((self.params,) + tuple(arrays)
                    + (self._last_tok, sub, self._k_pools, self._v_pools)
                    + (None,) * 7
                    + (wtables, self._wk_pools, self._wv_pools))
        args = ((self.params,) + tuple(jax.device_put(list(b.arrays)))
                + (self._last_tok, sub, self._k_pools, self._v_pools))
        if self.model.recurrent:
            # unified_step's full argument list: scales (or None), no
            # copy-on-write, then the recurrent state and the conv tail
            return args + (self._k_scales, self._v_scales, None, None, None,
                           self._ssm_state, self._conv_tail)
        if self.kv_quantized:
            args = args + (self._k_scales, self._v_scales)
        if self.prefix_share:
            # pending COW pairs ride this dispatch (executed before any
            # append); idle lanes self-copy the scratch block — a no-op
            R = self.max_batch
            cow_src = np.zeros((R,), np.int32)
            cow_dst = np.zeros((R,), np.int32)
            for j, (s, d) in enumerate(self._cow_pairs[:R]):
                cow_src[j] = s
                cow_dst[j] = d
            del self._cow_pairs[:R]
            args = args + tuple(jax.device_put(
                [cow_src, cow_dst, self._reset_tables.copy()]))
        return args

    @RecordEvent(SERVING_SPANS.walk)
    def _walk_ragged(self, b, toks, greedy_all, lens, ids0=None,
                     ids_burst=None):
        """Commit a fetched step on the host: lengths, prefix pages,
        draft acceptance, then every emitted token through
        _emit/_finish; returns the requests that finished in it. A row
        that had ended before the step ran (its EOS was learnt a step
        late) is only released: its tokens are dropped."""
        from ..enforce import enforce
        finished: List[Request] = []
        dec, pre, props_by_slot = b.dec, b.pre, b.props_by_slot
        if b.wfree:     # window pages this step was the last to read
            self.wfree_blocks.extend(b.wfree)
            b.wfree = []
        ran = b.q_lens > 0
        self._lens[ran] = lens[ran]
        wasted = [r for r in dec if r.done]
        for r in wasted:    # it rode this step; nothing writes its pages now
            self._release_slot(r)
        if wasted:
            self._prom.counter_inc("overlap_wasted_rows_total", len(wasted))
        for r in dec:
            r.decode_steps += 1
        for r in pre:
            r.prefill_done += b.grants.get(r.slot, 0)
            self._register_pages(r)
        for r in dec + pre:     # pass 1's positions of the rows that ran
            i = r.slot
            if r.keep_routing and not r.done and b.q_lens[i]:
                at, n = int(b.starts[i]), int(b.q_lens[i])
                r.routing[b.pos0[i]:b.pos0[i] + n] = \
                    ids0[:, at:at + n].transpose(1, 0, 2)
        if b.use_spec:
            for r in dec:
                i = r.slot
                props = props_by_slot.get(i)
                if not props:
                    continue  # plain row: emitted by the generic walk
                base = int(b.starts[i])
                acc = 0
                for j, p in enumerate(props):
                    if int(greedy_all[base + j]) != p:
                        break
                    acc += 1
                self.spec_proposed += len(props)
                self.spec_accepted += acc
                # KV rollback: only the verified prefix [pending,
                # props[:acc]] stays committed; the device wrote (and
                # returned lens for) all k+1 draft positions, but the
                # block table simply forgets the rejected tail — those
                # positions are past lens, never read, rewritten later
                self._lens[i] = int(b.pos0[i]) + acc + 1
                for tok in props[:acc] + [int(greedy_all[base + acc])]:
                    tok = self._check_tok(r, tok)
                    self._pending_tok[i] = tok
                    if self._emit(r, tok):
                        finished.append(r)
                        self._finish(r)
                        break
        for r in dec + [r for r in pre
                        if r.prefill_done >= len(r.prompt)]:
            if r.done or (b.use_spec and props_by_slot.get(r.slot)):
                continue  # ended before this step, or emitted above
            i, n = r.slot, 0
            for t in range(toks.shape[0]):
                if r.done:
                    break
                tok = self._check_tok(r, int(toks[t, i]))
                self._pending_tok[i] = tok
                n += 1
                if self._emit(r, tok):
                    finished.append(r)
                    self._finish(r)
                    break
            if r.keep_routing and n > 1:
                # burst pass j ran the row's position after pass j - 1's
                at = int(b.pos0[i] + b.q_lens[i])
                r.routing[at:at + n - 1] = ids_burst[:n - 1, :, i]
            # the next step may already be packed from `_advance`'s word:
            # a row that emitted all it was scheduled to (no EOS stopped
            # it short) must stand where the host said it would
            enforce(n < b.emit[i] or lens[i] == b.lens_after[i],
                    f"slot {i} (request {r.rid}): the device left "
                    f"{int(lens[i])} positions, the schedule said "
                    f"{int(b.lens_after[i])}", op="ServingEngine")
        self._note_completed(finished)
        return finished

    # -- observability -------------------------------------------------------
    def _note_pool_peak(self):
        """Sample pool pressure while this step's admissions HOLD their
        blocks — end-of-step sampling would miss requests that allocate
        and complete within one engine step (block 0 is the reserved
        scratch block, never allocatable)."""
        total_blocks = self._num_blocks - 1
        if total_blocks:
            self._prom.gauge_max(
                "kv_pool_utilization_peak",
                1.0 - self.free_pages() / total_blocks,
                help="high-water allocated fraction of the KV pool")
        if self._windowed:
            self._prom.gauge_max(
                "kv_window_pool_utilization_peak",
                self._window_utilization(),
                help="high-water allocated fraction of the window layers' "
                     "pool (pages that wait on the step in flight count "
                     "as allocated)")

    def _note_completed(self, finished):
        """Completion counters, events and the `serving_request_end`
        instant span of the requests a walk finished (inside the walk's
        span: a settle outside a step counts them too)."""
        # completed == finished SUCCESSFULLY: a request failed by its
        # own callback rides `finished` for page accounting but must not
        # count as a completion (it already counted in
        # callback_errors_total / serving_callback_error)
        self._prom.counter_inc(
            "requests_completed_total",
            sum(r.status == "ok" for r in finished),
            help="requests finished successfully")
        for r in finished:
            total_s = time.perf_counter() - r.submit_time
            with RecordEvent(REQUEST_SPANS.end, **dict(zip(
                    REQUEST_END_ATTRS,
                    (r.rid, r.status, len(r.output), r.decode_steps,
                     round(total_s * 1e6), r.preemptions)))):
                pass
            if r.status != "ok":
                continue
            self._prom.summary_observe(
                "request_seconds", total_s,
                help="submit-to-completion latency")
            # the JSONL timeline says what the trace says, from the same
            # record: the TTFT and the four phases it is made of
            self._emit_event(
                "serving_complete", rid=r.rid, tokens=len(r.output),
                ttft_s=r.ttft_s, **dict(zip(
                    ("queue_s", "wait_s", "prefill_s", "land_s"),
                    r.ttft_parts())))

    @RecordEvent(SERVING_SPANS.metrics)
    def _step_metrics(self, t_step0, tokens_before, n_pre, n_dec):
        """End-of-step telemetry: the prom gauges and counters, and the
        KV-scale poll. `n_pre`/`n_dec` are the dispatched step's rows,
        the tokens those this call walked."""
        prom = self._prom
        dt = max(time.perf_counter() - t_step0, 1e-9)
        emitted = self._tokens_total - tokens_before
        # end-of-step (post-free) pool state; the PEAK gauge is sampled
        # post-admit at the top of step(), where the blocks are held
        total = self._num_blocks - 1
        util = 1.0 - self.free_pages() / total if total else 0.0
        prom.gauge_set("kv_pool_utilization", util,
                       help="allocated fraction of the paged KV pool")
        prom.gauge_max("kv_pool_utilization_peak", util)
        if self._windowed:
            wutil = self._window_utilization()
            prom.gauge_set("kv_window_pool_utilization", wutil,
                           help="allocated fraction of the window layers' "
                                "pool")
            prom.gauge_max("kv_window_pool_utilization_peak", wutil)
            prom.counter_inc("window_pages_freed_total",
                             self.window_pages_freed - self._wfreed_reported,
                             help="window-lifetime pages given back behind "
                                  "the rows' windows")
            self._wfreed_reported = self.window_pages_freed
        if self.prefix_share:
            prom.gauge_set("kv_pages_shared",
                           int((self.refcount > 1).sum()),
                           help="pool pages referenced by >1 block table")
            prom.counter_inc("kv_cow_copies_total",
                             self.cow_copies - self._cow_reported,
                             help="shared KV pages copied on first write")
            self._cow_reported = self.cow_copies
        if self.spec_k > 0:
            prom.counter_inc("spec_proposed_total",
                             self.spec_proposed - self._spec_prop_reported,
                             help="draft tokens proposed for verification")
            prom.counter_inc("spec_accepted_total",
                             self.spec_accepted - self._spec_acc_reported,
                             help="draft tokens accepted (exact argmax "
                                  "match) — accepted/proposed is the "
                                  "speculation health rate")
            self._spec_prop_reported = self.spec_proposed
            self._spec_acc_reported = self.spec_accepted
        if self._ssm_state is not None:
            prom.gauge_set("ssm_state_bytes",
                           self._ssm_state.nbytes + self._conv_tail.nbytes,
                           help="recurrent state and conv tail held for "
                                "the slots")
            prom.counter_inc("ssm_state_resets_total",
                             self.ssm_resets - self._ssm_resets_reported,
                             help="slot states zeroed in-program (a row "
                                  "starting at position 0)")
            self._ssm_resets_reported = self.ssm_resets
        prom.gauge_set("queue_depth", len(self.queue))
        prom.gauge_set("running_requests",
                       sum(s is not None for s in self._slots),
                       help="slots occupied this step")
        prom.counter_inc("engine_steps_total", help="engine iterations")
        prom.gauge_set("dispatches_per_step",
                       self.dispatches / max(self.engine_steps, 1),
                       help="mean compiled dispatches per engine step")
        prom.counter_inc("tokens_total", emitted,
                         help="sampled tokens emitted")
        prom.gauge_set("prefill_decode_mix",
                       n_pre / (n_pre + n_dec) if (n_pre + n_dec) else 0.0,
                       help="prefill share of this step's active slots")
        prom.gauge_set("step_tokens_per_sec", emitted / dt,
                       help="tokens emitted by the last engine step / its "
                            "wall time")
        elapsed = max(time.perf_counter() - self._t_first_step, 1e-9)
        prom.gauge_set("tokens_per_sec", self._tokens_total / elapsed,
                       help="tokens emitted since the first engine step / "
                            "elapsed wall time")
        self._numerics_kv_poll()

    def metrics_text(self) -> str:
        """Prometheus text-format exposition of the engine's telemetry
        (TTFT, tokens/s, queue depth, KV-pool utilization, decode/prefill
        mix) — the payload serve_metrics() exposes over HTTP."""
        return self._prom.render()

    @property
    def prom(self):
        return self._prom

    # -- what an observer outside a step may read: a settled engine ---------
    slots = _settled_view("slots")
    lens = _settled_view("lens")
    k_pools = _settled_view("k_pools")
    v_pools = _settled_view("v_pools")
    k_scales = _settled_view("k_scales")
    v_scales = _settled_view("v_scales")
    ssm_state = _settled_view("ssm_state")
    conv_tail = _settled_view("conv_tail")
    wk_pools = _settled_view("wk_pools")
    wv_pools = _settled_view("wv_pools")

    def serve_metrics(self, port: Optional[int] = None):
        """Start (or return) the /metrics HTTP endpoint — which also
        serves ``/healthz`` (200 {"state": "ready"} when the engine is
        ready, 503 with the state otherwise: loading/draining/degraded).
        port None reads FLAGS_telemetry_prometheus_port (0 there =
        disabled -> None); port=0 binds an ephemeral port (read it from
        .port)."""
        if self._metrics_server is None:
            import weakref
            from ..observability import serve_registry
            # weak: the server thread outlives discarded engines — a
            # strong closure would pin the params + device KV pools of
            # every dead engine for the server's lifetime
            ref = weakref.ref(self)
            self._metrics_server = serve_registry(
                self._prom, port,
                health_fn=lambda: getattr(ref(), "health", "degraded"))
        return self._metrics_server


def generate_static_batch(params, cfg, prompts, max_new_tokens_list,
                          batch_size: int, temperature=0.0,
                          sort_by_len: bool = True):
    """Static-batching baseline for the serving bench: requests are
    processed in fixed batches; each batch prefills together and decodes
    until its LONGEST request finishes (idle tail slots keep computing) —
    the barrier waste continuous batching removes.

    Mixed prompt lengths: the STRONGEST static baseline is used — requests
    are bucketed by prompt length (sorted) and each batch pads prompts to
    its own max, so static pays minimal pad compute. Generation for a
    padded request conditions on the padded prompt (throughput baseline
    semantics; per-request token counts are unchanged)."""
    from ..models.generation import gpt_generate

    order = (sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
             if sort_by_len else list(range(len(prompts))))
    outs = [None] * len(prompts)
    for i in range(0, len(order), batch_size):
        idxs = order[i:i + batch_size]
        grp = [np.asarray(prompts[j], np.int32) for j in idxs]
        new = [max_new_tokens_list[j] for j in idxs]
        S = max(len(p) for p in grp)
        padded = np.zeros((len(grp), S), np.int32)
        for r, p in enumerate(grp):
            padded[r, :len(p)] = p  # right-pad to the bucket max
        res = gpt_generate(params, cfg, jnp.asarray(padded), max(new),
                           temperature=temperature)
        res = np.asarray(res)[:, S:]
        for r, (j, n) in enumerate(zip(idxs, new)):
            outs[j] = res[r, :n].tolist()
    return outs
