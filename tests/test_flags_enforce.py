"""Flags catalogue + enforce error system (VERDICT r2 #8): >=60 documented
flags, each observable — either bound to jax config (asserted via
jax.config readback) or consumed at a named call site (asserted by
behavior)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import enforce
from paddle_tpu.flags import _REGISTRY, flag, get_flags, set_flags


def _restore(name, value):
    set_flags({name: value})


def test_catalogue_size_and_docs():
    import paddle_tpu.distributed.check  # defines the comm-check flags
    assert len(_REGISTRY) >= 60, len(_REGISTRY)
    for name, f in _REGISTRY.items():
        assert f.help, f"flag {name} has no help text"


def _flag_uses():
    """Names some code outside a flag's own definition reads: the literal
    first argument of a `flag(...)` / `_flag(...)` / `get_flags(...)`
    call, or a `FLAGS_<name>` in any string, in `paddle_tpu/`,
    `chipbench/` and `examples/`."""
    import ast
    import os
    import re
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def literals(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node.value
        elif isinstance(node, (ast.List, ast.Tuple, ast.Set)):
            for e in node.elts:
                yield from literals(e)

    used = set()
    for top in ("paddle_tpu", "chipbench", "examples"):
        for dirpath, _, files in os.walk(os.path.join(repo, top)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, name)) as f:
                    tree = ast.parse(f.read())
                own = set()     # nodes inside a define_flag(...) call
                for node in ast.walk(tree):
                    if not isinstance(node, ast.Call):
                        continue
                    fn = getattr(node.func, "id",
                                 getattr(node.func, "attr", None))
                    if fn == "define_flag":
                        own.update(id(n) for n in ast.walk(node))
                    elif fn in ("flag", "_flag", "get_flags") and node.args:
                        used.update(s.removeprefix("FLAGS_")
                                    for s in literals(node.args[0]))
                for node in ast.walk(tree):
                    if (isinstance(node, ast.Constant)
                            and isinstance(node.value, str)
                            and id(node) not in own):
                        used.update(re.findall(r"FLAGS_(\w+)", node.value))
    return used


def test_every_flag_has_a_reader():
    """A flag that is accepted and does nothing is worse than the
    KeyError `set_flags` raises for an unknown name: every registered
    flag is bound by an `on_set` hook or read somewhere outside its own
    definition (PR 49 deleted the 22 that were not)."""
    import paddle_tpu.distributed.check  # defines the comm-check flags
    used = _flag_uses()
    unread = sorted(n for n, f in _REGISTRY.items()
                    if f.on_set is None
                    and n.removeprefix("FLAGS_") not in used)
    assert not unread, unread


JAX_BOUND = {
    "FLAGS_debug_nans": ("jax_debug_nans", True, False),
    "FLAGS_debug_infs": ("jax_debug_infs", True, False),
    "FLAGS_disable_jit": ("jax_disable_jit", True, False),
    "FLAGS_enable_x64": ("jax_enable_x64", True, False),
    "FLAGS_threefry_partitionable": ("jax_threefry_partitionable", False,
                                     True),
    "FLAGS_traceback_filtering": ("jax_traceback_filtering", "off", "auto"),
    "FLAGS_jit_cache_dir": ("jax_compilation_cache_dir", "/tmp/pt_cache",
                            ""),
}


@pytest.mark.parametrize("name", sorted(JAX_BOUND))
def test_jax_bound_flags(name, monkeypatch):
    cfg, on, off = JAX_BOUND[name]
    # the cache-dir binding stands only where the environment has not
    # placed the cache itself (test_jit_cache_dir_env_wins covers that)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = flag(name)
    try:
        set_flags({name: on})
        assert getattr(jax.config, cfg) == on
    finally:
        _restore(name, old)


def test_jit_cache_dir_env_wins(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, no code path sets another
    cache directory: FLAGS_jit_cache_dir (any value, and the "" the tests'
    autouse fixture re-applies) leaves jax's config alone."""
    before = jax.config.jax_compilation_cache_dir
    old = flag("jit_cache_dir")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    try:
        jax.config.update("jax_compilation_cache_dir",
                          "/placed/from/outside")
        for v in ("/tmp/pt_cache", ""):
            set_flags({"FLAGS_jit_cache_dir": v})
            assert (jax.config.jax_compilation_cache_dir
                    == "/placed/from/outside")
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        _restore("FLAGS_jit_cache_dir", old)
        jax.config.update("jax_compilation_cache_dir", before)


def test_repo_jit_cache_dir_is_fixed_and_inside_checkout():
    """The in-checkout cache path is a constant of the checkout — never
    built from tempfile, a pid or the time (the path is in the cache
    key) — and git-ignored."""
    import os
    from paddle_tpu.flags import REPO_JIT_CACHE_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert REPO_JIT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("name,env,want", [
    # the default asks the compiler for nothing
    ("default", {}, None),
    ("on", {"FLAGS_xla_latency_hiding_scheduler": "1"},
     ["--xla_tpu_enable_latency_hiding_scheduler=true",
      "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
      "--xla_enable_async_all_gather=true"]),
    # a name the operator set, either value, stays as they set it
    ("operator_false", {"FLAGS_xla_latency_hiding_scheduler": "1",
                        "LIBTPU_INIT_ARGS":
                        "--xla_enable_async_all_gather=false"},
     ["--xla_enable_async_all_gather=false",
      "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"]),
])
def test_what_import_leaves_in_libtpu_init_args(name, env, want):
    """`import paddle_tpu` in a fresh process, before any backend starts.
    The set holds no switch that makes an all-reduce asynchronous: on the
    benchmark's hybrid step those cost 3.5% (PERF.md PR 47)."""
    import os
    import subprocess
    import sys
    clean = {k: v for k, v in os.environ.items()
             if k not in ("LIBTPU_INIT_ARGS",
                          "FLAGS_xla_latency_hiding_scheduler")}
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, paddle_tpu; "
         "print(repr(os.environ.get('LIBTPU_INIT_ARGS')))"],
        env={**clean, **env, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, check=True, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    got = eval(out.stdout.strip().splitlines()[-1])
    if want is None:
        assert got is None
        return
    for token in want:
        assert got.split().count(token) == 1, (token, got)
    assert "--xla_enable_async_all_gather=true" not in got or \
        "--xla_enable_async_all_gather=false" not in got
    assert "all_reduce" not in got


def test_matmul_precision_bound():
    old = flag("tpu_matmul_precision")
    try:
        set_flags({"FLAGS_tpu_matmul_precision": "highest"})
        assert jax.config.jax_default_matmul_precision == "highest"
    finally:
        _restore("FLAGS_tpu_matmul_precision", old)


def test_deterministic_cascades():
    olds = {k: flag(k) for k in ("FLAGS_deterministic",
                                 "FLAGS_tpu_matmul_precision",
                                 "FLAGS_embedding_deterministic")}
    try:
        set_flags({"FLAGS_deterministic": True})
        assert flag("tpu_matmul_precision") == "highest"
        assert flag("embedding_deterministic") is True
    finally:
        set_flags(olds)


def test_dropout_rbg_flag_switches_engine():
    from paddle_tpu.random import next_mask_key
    old = flag("dropout_use_rbg")
    try:
        set_flags({"FLAGS_dropout_use_rbg": False})
        k1 = next_mask_key()
        set_flags({"FLAGS_dropout_use_rbg": True})
        k2 = next_mask_key()
        # threefry key data is (2,) uint32; rbg is (4,)
        assert jax.random.key_data(k1).size in (2,)
        assert jax.random.key_data(k2).size in (2, 4)  # rbg when supported
    finally:
        _restore("FLAGS_dropout_use_rbg", old)


def test_sr_moments_flag():
    import jax.numpy as jnp
    from paddle_tpu.optimizer.optimizer import _store_moment
    key = jax.random.PRNGKey(0)
    x = jnp.full((1024,), 1.0 + 1e-4, jnp.float32)  # below bf16 ulp of 1.0
    old = flag("bf16_stochastic_rounding_moments")
    try:
        set_flags({"FLAGS_bf16_stochastic_rounding_moments": False})
        nearest = _store_moment(x, jnp.bfloat16, key)
        assert float(jnp.mean(nearest.astype(jnp.float32))) == 1.0
        set_flags({"FLAGS_bf16_stochastic_rounding_moments": True})
        sr = _store_moment(x, jnp.bfloat16, key)
        assert float(jnp.mean(sr.astype(jnp.float32))) > 1.0  # some round up
    finally:
        _restore("FLAGS_bf16_stochastic_rounding_moments", old)


def test_amp_dtype_flag():
    from paddle_tpu.amp.auto_cast import _STATE, auto_cast
    old = flag("amp_dtype")
    try:
        set_flags({"FLAGS_amp_dtype": "float16"})
        with auto_cast(True):
            import jax.numpy as jnp
            assert _STATE.dtype in ("float16", jnp.float16)
    finally:
        _restore("FLAGS_amp_dtype", old)


def test_io_prefetch_flag():
    from paddle_tpu.io import DataLoader, Dataset

    class DS(Dataset):
        def __len__(self):
            return 2

        def __getitem__(self, i):
            return np.zeros(2)

    old = flag("io_prefetch_factor")
    try:
        set_flags({"FLAGS_io_prefetch_factor": 5})
        assert DataLoader(DS()).prefetch_factor == 5
    finally:
        _restore("FLAGS_io_prefetch_factor", old)


def test_dataloader_workers_flag():
    from paddle_tpu.io import DataLoader, Dataset

    class DS(Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            return np.zeros(2)

    old = flag("dataloader_num_workers")
    try:
        set_flags({"FLAGS_dataloader_num_workers": 2})
        assert DataLoader(DS()).num_workers == 2
        assert DataLoader(DS(), num_workers=0).num_workers == 0
    finally:
        _restore("FLAGS_dataloader_num_workers", old)


def test_store_timeout_flag():
    from paddle_tpu.distributed.store import TCPStore
    old = flag("tcp_store_timeout_s")
    try:
        set_flags({"FLAGS_tcp_store_timeout_s": 7})
        s = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
        assert s._timeout_ms == 7000
        s.close()
    finally:
        _restore("FLAGS_tcp_store_timeout_s", old)


def test_elastic_flags():
    from paddle_tpu.distributed.launch.elastic import ElasticManager
    from paddle_tpu.distributed.store import TCPStore
    olds = {k: flag(k) for k in ("FLAGS_elastic_heartbeat_interval_s",
                                 "FLAGS_elastic_hang_timeout_s")}
    try:
        set_flags({"FLAGS_elastic_heartbeat_interval_s": 9,
                   "FLAGS_elastic_hang_timeout_s": 77})
        store = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
        m = ElasticManager(store, "job", np=1)
        assert m.interval == 9 and m.timeout == 77
        store.close()
    finally:
        set_flags(olds)


def test_serving_flags():
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import gpt as G
    import jax.numpy as jnp
    olds = {k: flag(k) for k in ("FLAGS_paged_block_size",
                                 "FLAGS_serving_decode_burst",
                                 "FLAGS_serving_prefill_chunk")}
    cfg = G.GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                      num_heads=2, max_seq_len=32, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    try:
        set_flags({"FLAGS_paged_block_size": 4,
                   "FLAGS_serving_decode_burst": 3,
                   "FLAGS_serving_prefill_chunk": 8})
        eng = ServingEngine(params, cfg, num_blocks=8, max_blocks_per_seq=4)
        assert eng.bs == 4 and eng.decode_burst == 3 and eng.chunk == 8
    finally:
        set_flags(olds)


def test_dump_dir_flag(tmp_path):
    old = flag("dump_dir")
    try:
        set_flags({"FLAGS_dump_dir": str(tmp_path / "mirror")})
        paddle.save({"a": np.ones(2)}, str(tmp_path / "m.pdparams"))
        assert (tmp_path / "mirror" / "m.pdparams").exists()
    finally:
        _restore("FLAGS_dump_dir", old)


def test_profiler_dir_flag(tmp_path):
    from paddle_tpu.profiler.profiler import export_chrome_tracing
    old = flag("profiler_dir")
    try:
        set_flags({"FLAGS_profiler_dir": str(tmp_path / "prof")})
        handler = export_chrome_tracing()

        class FakeProf:
            step_num = 0
            _recorded = []

        handler(FakeProf())
        assert (tmp_path / "prof").exists()
    finally:
        _restore("FLAGS_profiler_dir", old)


def test_host_event_recorder_hook_flag():
    from paddle_tpu.profiler.utils import RecordEvent, collector
    old = flag("enable_host_event_recorder_hook")
    try:
        collector.clear()
        set_flags({"FLAGS_enable_host_event_recorder_hook": False})
        with RecordEvent("off"):
            pass
        assert not collector.drain()
        set_flags({"FLAGS_enable_host_event_recorder_hook": True})
        with RecordEvent("on"):
            pass
        evs = collector.drain()
        assert [e.name for e in evs] == ["on"]
    finally:
        _restore("FLAGS_enable_host_event_recorder_hook", old)


def test_watchdog_ceiling_flag():
    from paddle_tpu.distributed.watchdog import CommWatchdog
    olds = {k: flag(k) for k in ("FLAGS_stop_check_timeout",)}
    fired = []
    try:
        set_flags({"FLAGS_stop_check_timeout": 0})  # everything overruns
        wd = CommWatchdog(poll_interval=0.05,
                          on_timeout=lambda s, r: fired.append(s.tag))
        wd.start()
        import time
        with wd.watch("op", timeout=3600):
            time.sleep(0.4)
        wd.stop()
        assert fired, "ceiling did not fire"
    finally:
        set_flags(olds)


def test_dispatch_stats_flag():
    import jax.numpy as jnp
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import dispatch_stats
    old = flag("enable_dispatch_stats")
    q = jnp.ones((1, 8, 2, 4))
    try:
        dispatch_stats(reset=True)
        set_flags({"FLAGS_enable_dispatch_stats": False})
        F.scaled_dot_product_attention(q, q, q)
        assert "scaled_dot_product_attention" not in dispatch_stats()
        set_flags({"FLAGS_enable_dispatch_stats": True})
        F.scaled_dot_product_attention(q, q, q)
        assert dispatch_stats()["scaled_dot_product_attention"][
            "reference"] >= 1
    finally:
        _restore("FLAGS_enable_dispatch_stats", old)


# ---------------------------------------------------------------------------
# enforce
# ---------------------------------------------------------------------------
def test_enforce_taxonomy_and_context():
    x = np.zeros((2, 3))
    with pytest.raises(enforce.InvalidArgumentError) as ei:
        enforce.enforce(False, "rank mismatch", op="matmul", x=x)
    msg = str(ei.value)
    assert "[InvalidArgument]" in msg
    assert "[operator: matmul]" in msg
    assert "Tensor(shape=(2, 3)" in msg
    assert isinstance(ei.value, ValueError)  # ported except clauses work


def test_enforce_helpers():
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.enforce_eq(1, 2)
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.enforce_gt(1, 2)
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.enforce_in("x", {"a", "b"})
    with pytest.raises(enforce.InvalidArgumentError) as ei:
        enforce.enforce_shape(np.zeros((2, 3)), (2, None, 4), name="q")
    assert "q expects shape" in str(ei.value)
    enforce.enforce_shape(np.zeros((2, 9, 4)), (2, None, 4))  # passes


def test_enforce_call_stack_level():
    old = flag("call_stack_level")
    try:
        set_flags({"FLAGS_call_stack_level": 0})
        e0 = str(enforce.InvalidArgumentError("boom"))
        assert "[at:" not in e0 and "[call stack]" not in e0
        set_flags({"FLAGS_call_stack_level": 1})
        assert "[at:" in str(enforce.InvalidArgumentError("boom"))
        set_flags({"FLAGS_call_stack_level": 2})
        assert "[call stack]" in str(enforce.InvalidArgumentError("boom"))
    finally:
        _restore("FLAGS_call_stack_level", old)


def test_enforce_error_types_inherit_python_types():
    assert issubclass(enforce.NotFoundError, KeyError)
    assert issubclass(enforce.OutOfRangeError, IndexError)
    assert issubclass(enforce.UnimplementedError, NotImplementedError)
    assert issubclass(enforce.ExecutionTimeoutError, TimeoutError)


def test_public_api_raises_typed_contextual_errors():
    """VERDICT r3 #5: the top public ops validate shapes/axes through the
    enforce taxonomy — typed errors with op + tensor context, not bare
    ValueErrors."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x23 = jnp.zeros((2, 3))

    def check(fn, *frags):
        with pytest.raises(enforce.InvalidArgumentError) as ei:
            fn()
        msg = str(ei.value)
        assert "[InvalidArgument]" in msg
        for f in frags:
            assert f in msg, (f, msg)

    # 1 matmul: contraction mismatch, names both operand shapes
    check(lambda: paddle.matmul(x23, jnp.zeros((4, 5))),
          "[operator: matmul]", "(2, 3)", "(4, 5)")
    # 2 reshape: element-count mismatch
    check(lambda: paddle.reshape(x23, (4, 2)), "[operator: reshape]",
          "6 elements")
    # 3 transpose: bad permutation
    check(lambda: paddle.transpose(x23, (0, 0)), "[operator: transpose]")
    # 4 concat: rank mismatch + empty input
    check(lambda: paddle.concat([x23, jnp.zeros((2, 3, 1))]),
          "[operator: concat]", "rank")
    check(lambda: paddle.concat([]), "[operator: concat]")
    # 5 split: sections don't sum
    check(lambda: paddle.split(x23, [1, 4], axis=1), "[operator: split]",
          "sum")
    # 6 expand: -1 in a new leading dim
    check(lambda: paddle.expand(x23, (-1, 2, 3)), "[operator: expand]")
    # 7 linear: W in-dim mismatch
    check(lambda: F.linear(x23, jnp.zeros((4, 5))), "[operator: linear]")
    # 8 softmax: axis out of range
    check(lambda: F.softmax(x23, axis=5), "[operator: softmax]", "axis 5")
    # 9 cross_entropy: label shape mismatch
    check(lambda: F.cross_entropy(jnp.zeros((4, 10)),
                                  jnp.zeros((4, 2), jnp.int32)),
          "[operator: cross_entropy]", "labels")
    # 10 conv2d: channel/groups mismatch (typed, with both shapes)
    check(lambda: F.conv2d(jnp.zeros((1, 3, 8, 8)),
                           jnp.zeros((4, 5, 3, 3))),
          "[operator: conv2d]", "channels")
    # axis checks ride OutOfRange-compatible InvalidArgument too
    check(lambda: paddle.split(x23, 2, axis=7), "[operator: split]")


class TestPublicApiEnforceMessages:
    """Round-5 enforce sweep (VERDICT r4 ask-5): the public-API validation
    surface raises the typed taxonomy with [operator:] context. One test
    per top public op family; each asserts the error TYPE (including the
    builtin-compat base class) and the rendered op context."""

    def _check(self, fn, err, builtin, op_tag):
        with pytest.raises(err) as ei:
            fn()
        assert isinstance(ei.value, builtin)
        assert f"[operator: {op_tag}]" in str(ei.value)

    def test_optimizer_step_without_parameters(self):
        from paddle_tpu.enforce import PreconditionNotMetError
        self._check(lambda: paddle.optimizer.AdamW(1e-3).step(),
                    PreconditionNotMetError, RuntimeError, "Optimizer.step")

    def test_moe_layer_bad_dispatch_mode(self):
        from paddle_tpu.enforce import InvalidArgumentError
        from paddle_tpu.incubate.distributed.models.moe import MoELayer
        self._check(lambda: MoELayer(8, 16, 4, dispatch_mode="bogus"),
                    InvalidArgumentError, ValueError, "MoELayer")

    def test_mp_layer_indivisible_features(self):
        from paddle_tpu.enforce import InvalidArgumentError
        from paddle_tpu.distributed.topology import (
            CommunicateTopology, HybridCommunicateGroup,
            set_hybrid_communicate_group)
        topo = CommunicateTopology(
            ["data", "pipe", "sharding", "sep", "model"], [1, 1, 1, 1, 8])
        set_hybrid_communicate_group(HybridCommunicateGroup(topo))
        try:
            from paddle_tpu.distributed.fleet.layers.mpu import (
                ColumnParallelLinear)
            self._check(lambda: ColumnParallelLinear(16, 12),
                        InvalidArgumentError, ValueError,
                        "ColumnParallelLinear")
        finally:
            set_hybrid_communicate_group(None)

    def test_group_sharded_bad_level(self):
        from paddle_tpu.enforce import InvalidArgumentError
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.sharding.group_sharded import (
            build_sharded_train_step)
        mesh = dist.build_mesh({"sharding": 8})
        self._check(
            lambda: build_sharded_train_step(
                lambda p, x: 0.0, paddle.optimizer.AdamW(1e-3), mesh,
                level="zero9"),
            InvalidArgumentError, ValueError, "build_sharded_train_step")

    def test_fleet_hcg_before_init(self):
        from paddle_tpu.enforce import PreconditionNotMetError
        from paddle_tpu.distributed.fleet.fleet import Fleet
        self._check(lambda: Fleet().get_hybrid_communicate_group(),
                    PreconditionNotMetError, RuntimeError, "fleet")

    def test_gpt_config_bad_heads(self):
        from paddle_tpu.enforce import InvalidArgumentError
        from paddle_tpu.models.gpt import GPTConfig
        self._check(lambda: GPTConfig(hidden_size=100, num_heads=7),
                    InvalidArgumentError, ValueError, "GPTConfig")

    def test_amp_bad_level(self):
        from paddle_tpu.enforce import InvalidArgumentError
        self._check(lambda: paddle.amp.auto_cast(level="O9").__enter__(),
                    InvalidArgumentError, ValueError, "amp.auto_cast")

    def test_executor_bad_fetch_type(self):
        from paddle_tpu.enforce import InvalidTypeError
        import paddle_tpu.static as static
        prog = static.Program.from_callable(
            lambda x: x + 1, [static.InputSpec([2], "float32", "x")])
        exe = static.Executor()
        feed = {"x": np.zeros((2,), np.float32)}
        self._check(
            lambda: exe.run(prog, feed=feed, fetch_list=[object()]),
            InvalidTypeError, TypeError, "Executor.run")

    def test_set_device_unknown(self):
        from paddle_tpu.enforce import InvalidArgumentError
        self._check(lambda: paddle.device.set_device("quantum:0"),
                    InvalidArgumentError, ValueError, "set_device")

    def test_vision_pretrained_unavailable(self):
        from paddle_tpu.enforce import UnavailableError
        from paddle_tpu.vision.models import vgg16
        self._check(lambda: vgg16(pretrained=True),
                    UnavailableError, RuntimeError, "vision.models")

    def test_audio_window_and_signal_axis(self):
        from paddle_tpu.enforce import InvalidArgumentError
        import paddle_tpu.audio.functional as AF
        self._check(lambda: AF.get_window("warble", 16),
                    InvalidArgumentError, ValueError, "get_window")
        import paddle_tpu.signal as sig
        self._check(lambda: sig.frame(jnp.zeros((8,)), 4, 2, axis=1),
                    InvalidArgumentError, ValueError, "signal.frame")

    def test_pack_sequences_overflow(self):
        from paddle_tpu.enforce import OutOfRangeError
        from paddle_tpu.models.bert import pack_sequences
        self._check(lambda: pack_sequences([list(range(20))], seq_len=8),
                    OutOfRangeError, ValueError, "bert.pack_sequences")
