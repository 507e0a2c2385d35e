"""A mesh with ONE pipeline stage accumulates its microbatches (ISSUE 37).

At pp = 1 `num_microbatches > 1` is gradient accumulation: the hybrid step
scans over the microbatches, each iteration the forward AND the backward of
one, a gradient joining the sum where the backward makes it (ISSUE 47), and
reduces, clips and updates once. These tests hold that path to
(a) the same build at M = 1 on the whole batch and (b) the pipeline path of
a pp = 2 mesh, for label masks that differ between microbatches; hold the
lowered program's structure (no stage replay, no collective-permute); and
hold every program that stays on the pipeline path, pp > 1 and the
side-channel builds at pp = 1, byte for byte to the parent commit's.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.comm_overlap import CommOverlapConfig
from paddle_tpu.models import gpt as G, llama as L

CFG = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                  max_seq_len=16, dtype=jnp.float32)
MOE = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                  max_seq_len=16, dtype=jnp.float32, moe_num_experts=4)
LLAMA = L.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=4,
                      num_heads=4, num_kv_heads=2, max_seq_len=16,
                      dtype=jnp.float32)
PP1 = {"dp": 2, "pp": 1, "mp": 2}
PP2 = {"dp": 2, "pp": 2, "mp": 2}
DP1 = {"dp": 1, "pp": 1, "mp": 2}
LR = 1e-2
BETA1 = 0.9


def _mesh(dims):
    n = int(np.prod(list(dims.values())))
    return dist.build_mesh(dims, devices=jax.devices()[:n])


def _batch(masks):
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, CFG.vocab_size, (8, 16))
    labels = rng.randint(0, CFG.vocab_size, (8, 16))
    if masks == "ragged":
        # every microbatch of every split (M = 2, 4; dp = 2) keeps another
        # number of labels, one row none: a mean of microbatch means is
        # not the batch's mean here
        labels[0, :12] = -100
        labels[1, 3:] = -100
        labels[2, ::2] = -100
        labels[5, :] = -100
        labels[7, 5:9] = -100
    return jnp.asarray(tokens), jnp.asarray(labels)


def _train(model, cfg, dims, M, masks="ragged", steps=3, opt_kw=None, **kw):
    """(losses, first gradient by leaf, parameters after `steps`)."""
    tokens, labels = _batch(masks)
    opt = paddle.optimizer.AdamW(LR, beta1=BETA1, **(opt_kw or {}))
    step, shard, init = model.build_hybrid_train_step(
        cfg, _mesh(dims), opt, num_microbatches=M, **kw)
    params = shard(model.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    state = init(params)
    losses, grad = [], None
    for i in range(steps):
        params, state, loss = step(params, state, tokens, labels,
                                   jnp.float32(LR))
        losses.append(float(loss))
        if i == 0:   # moment1 = (1 - beta1) * the gradient the optimizer got
            slots = state.get("opt", state)["slots"]
            grad = jax.tree.map(
                lambda s: np.asarray(s["moment1"]) / (1 - BETA1), slots,
                is_leaf=lambda s: isinstance(s, dict) and "moment1" in s)
    return losses, grad, jax.tree.map(np.asarray, params)


def _assert_same(got, want, what, rtol=2e-6):
    """Losses and the first gradient to float32's accumulation order
    (`rtol`: a build that reduces in bfloat16 rounds the sum of two
    microbatches where the whole batch rounds one gradient); the
    parameters to 2% of one step's reach (AdamW divides a gradient by its
    own size, so an element whose gradient is rounding noise moves by a
    fraction of lr either way)."""
    np.testing.assert_allclose(got[0], want[0], rtol=rtol, err_msg=what)
    for name, k, tol in (("gradient", 1, None), ("parameters", 2, 0.02 * LR)):
        flat = jax.tree_util.tree_flatten_with_path(got[k])[0]
        for (path, a), b in zip(flat, jax.tree.leaves(want[k])):
            limit = tol or rtol * max(float(np.abs(b).max()), 1e-3)
            assert float(np.abs(a - b).max()) <= limit, (
                what, name, jax.tree_util.keystr(path))


@pytest.fixture(scope="module")
def whole_batch():
    """(a): the same build at M = 1, a mask each."""
    return {m: _train(G, CFG, PP1, 1, m) for m in ("uniform", "ragged")}


@pytest.fixture(scope="module")
def pipelined():
    """(b): the pipeline path, the same model on a pp = 2 mesh."""
    return {m: _train(G, CFG, PP2, 2, m) for m in ("uniform", "ragged")}


@pytest.mark.parametrize("masks", ["uniform", "ragged"])
@pytest.mark.parametrize("M", [2, 4])
def test_accumulation_equals_whole_batch_and_pipeline(M, masks, whole_batch,
                                                      pipelined):
    got = _train(G, CFG, PP1, M, masks)
    _assert_same(got, whole_batch[masks], f"M={M} against M=1")
    _assert_same(got, pipelined[masks], f"M={M} against pp=2")


def test_a_mean_of_microbatch_means_would_differ(whole_batch):
    """The ragged masks do tell the two apart: the whole batch's loss is
    not the mean of its microbatches' mean losses."""
    tokens, labels = _batch("ragged")
    opt = paddle.optimizer.AdamW(LR, beta1=BETA1)
    step, shard, init = G.build_hybrid_train_step(
        CFG, _mesh(PP1), opt, donate=False)   # both halves start from params
    params = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    means = []
    for half in (slice(0, 2), slice(2, 4)):   # M = 2 on each dp rank
        rows = np.r_[np.arange(8)[half], np.arange(8)[4:][half]]
        _, _, loss = step(params, init(params), tokens[rows], labels[rows],
                          jnp.float32(LR))
        means.append(float(loss))
    assert abs(np.mean(means) - whole_batch["ragged"][0][0]) > 1e-3


@pytest.mark.parametrize("name,kw,opt_kw", [
    ("zero1", dict(zero_stage=1), None),
    ("zero2", dict(zero_stage=2), None),
    ("seq_parallel", dict(mp_overlap="seq_parallel"), None),
    ("flash", dict(flash_attention=True), None),
    ("telemetry", dict(telemetry=paddle.observability.TelemetryConfig(
        interval=4)), None),
    ("clip", {}, dict(grad_clip=paddle.nn.ClipGradByGlobalNorm(0.05))),
    ("interleaved", dict(virtual_pp=2), None),
    ("zbh1", dict(schedule="ZBH1"), None),
    # the reduction's wire dtype: the sum is cast where it is reduced;
    # float16's keeps the parent's formula (cast, sum, THEN divide: below)
    ("reduce_bf16", dict(grad_reduce_dtype=jnp.bfloat16), None),
    ("reduce_fp16", dict(grad_reduce_dtype=jnp.float16), None),
    # dp = 1: the same sum, nothing to reduce or divide
    ("dp1_mp2", dict(dims=DP1), None),
])
def test_accumulation_composes(name, kw, opt_kw):
    """What lives in the engine or inside a block takes the new path as it
    is: M = 2 follows the same build at M = 1."""
    kw = dict(kw)
    dims = kw.pop("dims", PP1)
    want = _train(G, CFG, dims, 1, opt_kw=opt_kw, **kw)
    got = _train(G, CFG, dims, 2, opt_kw=opt_kw, **kw)
    # bfloat16 keeps 8 bits, float16 11: M = 2 rounds g1 + g2 where M = 1
    # rounds g
    _assert_same(got, want, name, rtol={
        "reduce_bf16": 2 ** -7, "reduce_fp16": 2 ** -10}.get(name, 2e-6))


def test_llama_accumulates_too():
    got = _train(L, LLAMA, PP1, 2)
    _assert_same(got, _train(L, LLAMA, PP1, 1), "llama M=2 against M=1")
    _assert_same(got, _train(L, LLAMA, PP2, 2), "llama M=2 against pp=2")


# -- the lowered program's structure ----------------------------------------
def _lowered(model, cfg, dims, **kw):
    opt = paddle.optimizer.AdamW(LR)
    step, _, init = model.build_hybrid_train_step(cfg, _mesh(dims), opt, **kw)
    ex = jax.eval_shape(
        lambda: model.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    tok = jnp.zeros((8, 16), jnp.int32)
    args = (ex, init.abstract(ex), tok, tok, jnp.float32(LR))
    return step, args


def _subjaxprs(eqn):
    return list(jax.core.jaxprs_in_params(eqn.params))


def _count(jaxpr, pred):
    return sum(pred(e) + sum(_count(j, pred) for j in _subjaxprs(e))
               for e in jaxpr.eqns)


def _mp_allreduces_a_block_pass(jaxpr):
    """All-reduces over mp inside the block scans: the scans that hold no
    further scan (the microbatch scan and the pipeline's tick scan hold the
    block scans; a block's body holds none)."""
    is_scan = lambda e: e.primitive.name == "scan"
    is_mp = lambda e: (e.primitive.name.startswith("psum")
                       and "mp" in str(e.params.get("axes")))
    n = 0
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn):
            if is_scan(eqn) and not _count(sub, is_scan):
                n += _count(sub, is_mp)
            else:
                n += _mp_allreduces_a_block_pass(sub)
    return n


def test_one_stage_step_has_no_replay_and_no_permute():
    step, args = _lowered(G, CFG, PP1, num_microbatches=2)
    text = step.lower(*args).as_text()
    assert "collective_permute" not in text
    # seven GEMM sites (qkv, scores, values, proj, fc1, fc2 in the block
    # scan's body; the head), each once forward and twice backward. A
    # block keeps its GEMMs' outputs (gpt.ONE_STAGE_SAVE) and replays the
    # element-wise work between them; without the flash kernel (and the
    # (out, lse) it keeps) that takes in the composed attention's two
    # products and nothing else: no projection, no collective
    assert text.count("stablehlo.dot_general") == 3 * 7 + 2
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    assert _mp_allreduces_a_block_pass(jaxpr) == 4   # not six

    piped, pargs = _lowered(G, CFG, PP2, num_microbatches=2)
    ptext = piped.lower(*pargs).as_text()
    assert "collective_permute" in ptext
    assert ptext.count("stablehlo.dot_general") == 3 * 7 + 6
    assert _mp_allreduces_a_block_pass(
        jax.make_jaxpr(piped)(*pargs).jaxpr) == 6


def test_one_stage_flash_block_runs_its_kernel_once():
    """With the flash kernel its (out, lse) are kept too (the chip's case,
    tests/test_chip_compile.py): the forward kernel runs once a block, not
    again in the backward, as the pipeline's stage replay runs it."""
    def flash_forwards(dims):
        step, args = _lowered(G, CFG, dims, num_microbatches=2,
                              flash_attention=True)
        return _count(jax.make_jaxpr(step)(*args).jaxpr, lambda e: (
            e.primitive.name == "pallas_call"
            and e.params["name"] == "flash_fwd"))
    assert flash_forwards(PP1) == 1
    assert flash_forwards(PP2) == 2


def _dp_psums(jaxpr, scans=()):
    """(enclosing scans' lengths, operand avals, a division follows) of
    every psum over dp."""
    out = []
    for e in jaxpr.eqns:
        if (e.primitive.name.startswith("psum")
                and "dp" in str(e.params.get("axes"))):
            out.append((scans, [v.aval for v in e.invars], any(
                u.primitive.name == "div" and e.outvars[0] in u.invars
                for u in jaxpr.eqns)))
        inner = (scans + (e.params["length"],)
                 if e.primitive.name == "scan" else scans)
        for sub in _subjaxprs(e):
            out += _dp_psums(sub, inner)
    return out


def _scans(jaxpr, length):
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "scan" and e.params["length"] == length:
            out.append(e)
        for sub in _subjaxprs(e):
            out += _scans(sub, length)
    return out


@pytest.mark.parametrize("name,dims,M,kw", [
    ("M2", PP1, 2, {}), ("M4", PP1, 4, {}),
    # ONE accumulation path: ZeRO-1 and dp = 1 sum the same way
    ("zero1", PP1, 2, dict(zero_stage=1)), ("dp1", DP1, 2, {}),
])
def test_a_gradient_joins_the_sum_inside_the_backward(name, dims, M, kw):
    """The microbatch scan's body adds no stacked gradient to its carry
    (a pass over the whole stack a microbatch, as the parent had): the
    stack's sum rides the BACKWARD layer scan's carry, a layer's slice
    updated where it lies, and that scan hands out no stacked gradient."""
    step, args = _lowered(G, CFG, dims, num_microbatches=M, **kw)
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    stack = jax.tree.leaves(args[0]["blocks"])
    micro = _scans(jaxpr, M)[0]           # the outermost of that length
    body = micro.params["jaxpr"].jaxpr
    n = micro.params["num_consts"]
    carried_in = body.invars[n:n + micro.params["num_carry"]]
    (forward,) = [e for e in body.eqns if e.primitive.name == "scan"
                  and not e.params["reverse"]]
    uses = lambda e, vs: any(v is u for v in vs for u in e.invars)
    sums = [v for v in carried_in if uses(forward, [v])]
    assert len(sums) == len(stack)   # the layer scan takes the stack's sum
    added_outside = [e for e in body.eqns if uses(e, sums)
                     and e.primitive.name.startswith("add")]
    assert not added_outside, added_outside
    (backward,) = [e for e in _scans(body, CFG.num_layers)
                   if e.params["reverse"]]
    assert backward.params["num_carry"] == 1 + len(stack)
    assert len(backward.outvars) == backward.params["num_carry"]   # no ys
    updates = [e.outvars[0].aval.shape[0]
               for e in backward.params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "dynamic_update_slice"]
    assert updates == [CFG.num_layers] * len(stack)   # each leaf's shard


@pytest.mark.parametrize("name,dims,M,kw,plain", [
    ("M1", PP1, 1, {}, True), ("M2", PP1, 2, {}, True),
    ("M4", PP1, 4, {}, True), ("dp1", DP1, 2, {}, False),
    ("zero1", PP1, 2, dict(zero_stage=1), False),
    ("pipeline", PP2, 2, {}, False),
])
def test_no_psum_over_dp_inside_a_scan(name, dims, M, kw, plain):
    """No scan holds a psum over dp (that would be M x the wire bytes);
    where the reduction is the plain one every leaf meets ONE, of one
    array, after the last backward."""
    step, args = _lowered(G, CFG, dims, num_microbatches=M, **kw)
    psums = _dp_psums(jax.make_jaxpr(step)(*args).jaxpr)
    assert psums and not [p for p in psums if p[0]], psums
    if plain:
        assert all(len(avals) == 1 for _, avals, _ in psums)
        assert len(psums) == len(jax.tree.leaves(args[0])) + 1   # the loss


@pytest.mark.parametrize("name,M,kw,wire,divided_after", [
    # ranks a power of two, float32's exponent range all the way: the
    # mean's division is made as a microbatch's gradient joins the sum
    # (the same bits), and the all-reduce is a psum
    ("plain", 2, {}, jnp.float32, False),
    ("reduce_bf16", 2, dict(grad_reduce_dtype=jnp.bfloat16), jnp.bfloat16,
     False),
    # float16 on the wire: a small g / ranks would lose bits before the
    # cast, so the parent's formula stands: cast, sum, THEN divide
    ("reduce_fp16", 2, dict(grad_reduce_dtype=jnp.float16), jnp.float16,
     True),
    # M = 1 sums nothing, so there is nowhere to fold a division into
    ("M1", 1, {}, jnp.float32, True),
])
def test_where_the_mean_divides(name, M, kw, wire, divided_after):
    step, args = _lowered(G, CFG, PP1, num_microbatches=M, **kw)
    psums = [p for p in _dp_psums(jax.make_jaxpr(step)(*args).jaxpr)
             if p[1][0].shape]          # the gradients', not the loss's
    assert len(psums) == len(jax.tree.leaves(args[0]))
    assert {p[1][0].dtype for p in psums} == {jnp.dtype(wire)}
    assert {p[2] for p in psums} == {divided_after}


# sha256 of the lowered step, recorded on the PARENT commit (ISSUE 37:
# bbc0cbc): what stays on the pipeline path lowers to the parent's text.
# Private functions are renumbered in order of appearance first: their
# names carry a counter of everything traced before them, which the
# blocks' checkpoint_name tags (no operation of the program) move.
PARENT = {
    "pp2-1f1b": "b670ab331aa7e7c3", "pp2-interleaved": "21f8f509e814a539",
    "pp2-zbh1": "bf6644846dadc6f0", "pp1-fp8": "d4c7b1abdb8fdd93",
    "pp1-moe": "8cc4fd7cf310365e", "pp1-act": "231c3b821dbbcfb5",
    "pp1-zero3": "07384ec86b6c386e", "pp1-comm-overlap": "d29da11bf2e1bd73",
    "llama-pp2": "12d7d3449cf7a578", "llama-pp1-fp8": "afaed277c21b8fa1",
}
PIPELINE_PATH = {
    "pp2-1f1b": (G, CFG, PP2, dict(num_microbatches=2)),
    "pp2-interleaved": (G, CFG, PP2, dict(num_microbatches=4, virtual_pp=2)),
    "pp2-zbh1": (G, CFG, PP2, dict(num_microbatches=2, schedule="ZBH1")),
    # the side channels of the pipeline: each keeps its path at pp = 1
    "pp1-fp8": (G, CFG, PP1, dict(num_microbatches=2, fp8=True)),
    "pp1-moe": (G, MOE, {"dp": 2, "ep": 2, "pp": 1, "mp": 2},
                dict(num_microbatches=2)),
    "pp1-act": (G, CFG, PP1, dict(num_microbatches=2, numerics=True)),
    "pp1-zero3": (G, CFG, PP1, dict(num_microbatches=2, zero_stage=3)),
    "pp1-comm-overlap": (G, CFG, PP1, dict(
        num_microbatches=2,
        comm_overlap=CommOverlapConfig(bucket_mb=0.001, microbatches=2))),
    "llama-pp2": (L, LLAMA, PP2, dict(num_microbatches=2)),
    "llama-pp1-fp8": (L, LLAMA, PP1, dict(num_microbatches=2, fp8=True)),
}


@pytest.mark.parametrize("name", sorted(PIPELINE_PATH))
def test_pipeline_path_lowers_to_the_parents_text(name):
    model, cfg, dims, kw = PIPELINE_PATH[name]
    # the recorded text is the parent's, whose builders did not donate:
    # donation adds the arguments' aliasing attributes and nothing else
    step, args = _lowered(model, cfg, dims, donate=False, **kw)
    text = step.lower(*args).as_text()
    assert "collective_permute" in text   # the pipeline is still built
    seen = {}
    text = re.sub(r"@\w+?_\d+\b",
                  lambda m: seen.setdefault(m.group(), f"@f{len(seen)}"), text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[name]
