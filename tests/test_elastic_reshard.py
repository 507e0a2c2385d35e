"""Elastic-scale resilience tests (ISSUE 7): topology-agnostic checkpoints
(schema-v2 layout metadata, v1 byte-compat), reshard-on-load across mesh
changes (pp remap, zero1 on<->off, dp/mp regroup), carry remap policies
(comm_ef reset + JSONL event, telemetry reinit, fp8 amax-tick rescale),
mid-save torn-chunk faults, checkpoint-root hardening, the inspect CLI and
the resilient driver's reshard-on-resume path.

Fast tier: pure-checkpoint and driver-level tests (no hybrid-engine
compiles). The hybrid-engine elastic legs (pp-shrink bitwise parity, the
ISSUE dp2pp2mp2<->dp4pp2mp1 pair, zero1 toggle, fp8 carries) and the
2-process elastic spawn ride the slow tier.
"""

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.checkpoint.metadata import Metadata
from paddle_tpu.distributed.resilience import (
    FaultInjected, commit_checkpoint, faults, latest_checkpoint)
from paddle_tpu.distributed.resilience.commit import checkpoint_step
from paddle_tpu.distributed.resilience.driver import run_resilient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mesh_of(dims, n=None):
    devs = jax.devices()
    return dist.build_mesh(dims, devices=None if n is None else devs[:n])


def shard(x, mesh, spec):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))


def _arm(spec):
    paddle.set_flags({"FLAGS_fault_inject": spec})


@pytest.fixture
def reshard_on():
    paddle.set_flags({"FLAGS_ckpt_reshard": True})
    yield
    paddle.set_flags({"FLAGS_ckpt_reshard": False})


@pytest.fixture
def jsonl_events(tmp_path):
    """Route JSONL events to a temp file and hand back a reader."""
    p = tmp_path / "events.jsonl"
    paddle.set_flags({"FLAGS_telemetry_jsonl": str(p)})
    yield lambda: ([json.loads(l) for l in p.read_text().splitlines()]
                   if p.exists() else [])
    paddle.set_flags({"FLAGS_telemetry_jsonl": ""})


# -- schema / byte-compat ----------------------------------------------------
def test_flags_off_metadata_is_v1_byte_compatible(tmp_path):
    """Default (FLAGS_ckpt_reshard off) metadata must carry EXACTLY the v1
    instance fields — the pickle is byte-identical to the pre-elastic
    format (same class, same __dict__)."""
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"w": shard(jnp.ones((8, 2)), mesh, P("dp"))},
                         str(tmp_path))
    md = ckpt.load_metadata(str(tmp_path))
    assert sorted(md.__dict__) == ["flat_mapping", "misc",
                                   "state_dict_metadata", "storage_metadata"]
    # v2 accessors fall back to the class defaults
    assert md.schema_version == 1 and md.layout is None


def test_v1_pickle_loads_with_compat_defaults(tmp_path):
    """A checkpoint written before the schema existed (simulated by
    stripping the v2 fields) still loads, reports v1, and never triggers
    the reshard path."""
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"w": shard(jnp.full((4,), 7.0), mesh, P())},
                         str(tmp_path))
    raw = pickle.loads((tmp_path / "0.metadata").read_bytes())
    raw.__dict__.pop("schema_version", None)
    raw.__dict__.pop("layout", None)
    (tmp_path / "0.metadata").write_bytes(pickle.dumps(raw))
    md = ckpt.load_metadata(str(tmp_path))
    assert md.schema_version == 1 and md.layout is None
    tgt = {"w": shard(jnp.zeros((4,)), mesh, P())}
    assert ckpt.layout_mismatch(md, tgt) is None  # nothing to compare
    out = ckpt.load_state_dict(tgt, str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full((4,), 7.0))


def test_layout_recorded_with_flag_on(tmp_path, reshard_on):
    mesh = mesh_of({"dp": 2, "mp": 4})
    state = {"m": {"w": shard(jnp.ones((8, 4)), mesh, P("mp", None)),
                   "b": shard(jnp.ones((4,)), mesh, P())},
             "step": 3}
    ckpt.save_state_dict(state, str(tmp_path),
                         layout_extra={"zero1": False})
    md = ckpt.load_metadata(str(tmp_path))
    assert md.schema_version == 2
    lay = md.layout
    assert lay.mesh == {"dp": 2, "mp": 4}
    assert lay.specs["m.w"] == ("mp", None)
    assert lay.global_shapes["m.w"] == (8, 4)
    assert lay.replication["m.w"] == 2   # replicated over dp only
    assert lay.replication["m.b"] == 8   # fully replicated
    assert lay.extra["zero1"] is False


# -- reshard-on-load: pure checkpoint level ----------------------------------
def test_reshard_load_onto_disjoint_mesh(tmp_path, reshard_on):
    mesh_a = mesh_of({"dp": 2, "mp": 4})
    w = np.arange(64, dtype=np.float32).reshape(8, 8)
    ckpt.save_state_dict({"w": shard(w, mesh_a, P("mp", "dp"))},
                         str(tmp_path))
    mesh_b = mesh_of({"x": 4}, n=4)
    tgt = {"w": shard(jnp.zeros((8, 8)), mesh_b, P(None, "x"))}
    md = ckpt.load_metadata(str(tmp_path))
    mm = ckpt.layout_mismatch(md, tgt)
    assert mm is not None and "mesh" in mm
    out = ckpt.load_resharded(tgt, str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["w"]), w)
    assert out["w"].sharding.spec == P(None, "x")


def test_zero1_toggle_roundtrip(tmp_path, reshard_on):
    """dp-sharded optimizer moments (zero1 on) load bitwise into replicated
    targets (zero1 off) and back — global offsets make the two forms
    interchangeable."""
    mesh = mesh_of({"dp": 8})
    m = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    ckpt.save_state_dict({"opt": {"m1": shard(m, mesh, P("dp"))}}, d1,
                         layout_extra={"zero1": True})
    tgt = {"opt": {"m1": shard(jnp.zeros((16, 4)), mesh, P())}}
    out = ckpt.load_resharded(tgt, d1, layout_extra={"zero1": False})
    np.testing.assert_array_equal(np.asarray(out["opt"]["m1"]), m)
    # and back: replicated save -> dp-sharded load
    ckpt.save_state_dict(out, d2, layout_extra={"zero1": False})
    tgt2 = {"opt": {"m1": shard(jnp.zeros((16, 4)), mesh, P("dp"))}}
    out2 = ckpt.load_resharded(tgt2, d2, layout_extra={"zero1": True})
    np.testing.assert_array_equal(np.asarray(out2["opt"]["m1"]), m)


def test_pp_vpp_relayout_on_load(tmp_path, reshard_on):
    """Stacked-[L] block leaves stored in (pp=2, vpp=2) chunk-major order
    load into a (pp=2, vpp=1) template in canonical order — the in-stream
    half of pp_adaptor, driven purely by the recorded layout."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils.spmd_pipeline \
        import vpp_block_permutation
    mesh = mesh_of({"pp": 2}, n=2)
    L = 8
    canon = np.arange(L * 3, dtype=np.float32).reshape(L, 3)
    order = vpp_block_permutation(L, 2, 2)
    stored = canon[np.asarray(order)]
    src_pp = {"num_layers": L, "pp": 2, "vpp": 2,
              "stacked_components": ["blocks"]}
    dst_pp = {"num_layers": L, "pp": 2, "vpp": 1,
              "stacked_components": ["blocks"]}
    ckpt.save_state_dict(
        {"blocks": {"w": shard(stored, mesh, P("pp"))},
         "lnf": shard(jnp.ones((3,)), mesh, P())},
        str(tmp_path), layout_extra={"pp": src_pp})
    tgt = {"blocks": {"w": shard(jnp.zeros((L, 3)), mesh, P("pp"))},
           "lnf": shard(jnp.zeros((3,)), mesh, P())}
    md = ckpt.load_metadata(str(tmp_path))
    assert ckpt.layout_mismatch(md, tgt, layout_extra={"pp": dst_pp})[
        "pp_relayout"] is True
    out = ckpt.load_resharded(tgt, str(tmp_path),
                              layout_extra={"pp": dst_pp})
    np.testing.assert_array_equal(np.asarray(out["blocks"]["w"]), canon)
    # non-stacked leaves pass through untouched
    np.testing.assert_array_equal(np.asarray(out["lnf"]), np.ones((3,)))
    # identity relayout (same layout both sides) short-circuits
    same = ckpt.load_resharded(
        {"blocks": {"w": shard(jnp.zeros((L, 3)), mesh, P("pp"))},
         "lnf": shard(jnp.zeros((3,)), mesh, P())},
        str(tmp_path), layout_extra={"pp": src_pp})
    np.testing.assert_array_equal(np.asarray(same["blocks"]["w"]), stored)


def test_comm_ef_reset_on_plan_change_with_event(tmp_path, reshard_on,
                                                 jsonl_events):
    """comm_ef residuals reset to the template's zeros (with an explicit
    JSONL event) when the bucket plan changed; identical plans load."""
    mesh = mesh_of({"dp": 8})
    res = np.random.RandomState(1).randn(64).astype(np.float32)
    plan_a = {"n_dev": 8, "buckets": [64]}
    ckpt.save_state_dict(
        {"state": {"comm_ef": {"0": shard(res, mesh, P("dp"))}}},
        str(tmp_path), layout_extra={"comm_plan": plan_a})
    # same plan -> residuals transfer
    tgt = {"state": {"comm_ef": {"0": shard(jnp.zeros((64,)), mesh,
                                            P("dp"))}}}
    out = ckpt.load_resharded(tgt, str(tmp_path),
                              layout_extra={"comm_plan": plan_a})
    np.testing.assert_array_equal(np.asarray(out["state"]["comm_ef"]["0"]),
                                  res)
    # changed plan -> reset to the template zeros + event
    tgt2 = {"state": {"comm_ef": {"0": shard(jnp.zeros((64,)), mesh,
                                             P("dp"))}}}
    out2 = ckpt.load_resharded(tgt2, str(tmp_path),
                               layout_extra={"comm_plan": {"n_dev": 4,
                                                           "buckets": [64]}})
    np.testing.assert_array_equal(np.asarray(out2["state"]["comm_ef"]["0"]),
                                  np.zeros((64,)))
    events = [e for e in jsonl_events() if e["event"] == "ckpt_carry_reset"]
    assert events and events[-1]["reason"] == "plan_changed"
    assert events[-1]["key"] == "state.comm_ef.0"


def test_comm_ef_missing_from_checkpoint_resets(tmp_path, reshard_on):
    """A template whose comm_ef carry does not exist in the checkpoint at
    all (saved without comm overlap) keeps its fresh zeros instead of
    raising KeyError."""
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"state": {"w": shard(jnp.ones((8,)), mesh,
                                               P("dp"))}}, str(tmp_path))
    tgt = {"state": {"w": shard(jnp.zeros((8,)), mesh, P("dp")),
                     "comm_ef": {"0": shard(jnp.zeros((16,)), mesh,
                                            P("dp"))}}}
    out = ckpt.load_resharded(tgt, str(tmp_path),
                              layout_extra={"comm_plan": {"n_dev": 8}})
    np.testing.assert_array_equal(np.asarray(out["state"]["w"]),
                                  np.ones((8,)))
    np.testing.assert_array_equal(np.asarray(out["state"]["comm_ef"]["0"]),
                                  np.zeros((16,)))


def test_comm_ef_resets_on_mesh_regroup(tmp_path, reshard_on, jsonl_events):
    """Residuals are LOCAL rounding errors: a mesh regroup at the same
    device count (dp8 -> dp4·mp2) reassigns shards to ranks even though
    the plan fingerprint and every global shape are unchanged — they must
    reset, not transfer verbatim."""
    res = np.random.RandomState(2).randn(64).astype(np.float32)
    plan = {"n_dev": 8, "buckets": [64]}
    ckpt.save_state_dict(
        {"state": {"comm_ef": {"0": shard(res, mesh_of({"dp": 8}),
                                          P("dp"))}}},
        str(tmp_path), layout_extra={"comm_plan": plan})
    mesh_b = mesh_of({"dp": 4, "mp": 2})
    tgt = {"state": {"comm_ef": {"0": shard(jnp.zeros((64,)), mesh_b,
                                            P("dp"))}}}
    out = ckpt.load_resharded(tgt, str(tmp_path),
                              layout_extra={"comm_plan": plan})
    np.testing.assert_array_equal(np.asarray(out["state"]["comm_ef"]["0"]),
                                  np.zeros((64,)))
    ev = [e for e in jsonl_events() if e["event"] == "ckpt_carry_reset"]
    assert ev and ev[-1]["reason"] == "mesh_changed"


def test_comm_ef_resets_on_pp_relayout(tmp_path, reshard_on, jsonl_events):
    """A (pp, vpp) relayout at a fixed mesh and plan reorders which layers
    back each rank's buckets — the residuals' owners changed, so they
    reset (same-mesh same-plan loads still transfer, asserted above)."""
    mesh = mesh_of({"dp": 8})
    res = np.random.RandomState(3).randn(64).astype(np.float32)
    plan = {"n_dev": 8, "buckets": [64]}
    pp_a = {"num_layers": 4, "pp": 2, "vpp": 1}
    ckpt.save_state_dict(
        {"state": {"comm_ef": {"0": shard(res, mesh, P("dp"))}}},
        str(tmp_path), layout_extra={"comm_plan": plan, "pp": pp_a})
    tgt = {"state": {"comm_ef": {"0": shard(jnp.zeros((64,)), mesh,
                                            P("dp"))}}}
    out = ckpt.load_resharded(
        tgt, str(tmp_path),
        layout_extra={"comm_plan": plan,
                      "pp": {"num_layers": 4, "pp": 2, "vpp": 2}})
    np.testing.assert_array_equal(np.asarray(out["state"]["comm_ef"]["0"]),
                                  np.zeros((64,)))
    ev = [e for e in jsonl_events() if e["event"] == "ckpt_carry_reset"]
    assert ev and ev[-1]["reason"] == "pp_relayout"


def test_telemetry_reinit_policy(tmp_path, reshard_on, jsonl_events):
    """Telemetry ring buffers reinitialize on the reshard path — stale
    per-topology series (comms bytes) must not leak onto the new mesh."""
    mesh = mesh_of({"dp": 8})
    buf = {"data": shard(jnp.full((4, 3), 9.0), mesh, P()),
           "count": shard(jnp.asarray(7, jnp.int32), mesh, P())}
    ckpt.save_state_dict({"state": {"telemetry": buf,
                                    "w": shard(jnp.ones((8,)), mesh,
                                               P("dp"))}}, str(tmp_path))
    tgt = {"state": {"telemetry": {
        "data": shard(jnp.zeros((4, 3)), mesh, P()),
        "count": shard(jnp.asarray(0, jnp.int32), mesh, P())},
        "w": shard(jnp.zeros((8,)), mesh, P("dp"))}}
    out = ckpt.load_resharded(tgt, str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["state"]["telemetry"]
                                             ["data"]), np.zeros((4, 3)))
    assert int(out["state"]["telemetry"]["count"]) == 0
    np.testing.assert_array_equal(np.asarray(out["state"]["w"]),
                                  np.ones((8,)))
    assert any(e["event"] == "ckpt_carry_reinit" for e in jsonl_events())


def test_fp8_amax_ticks_rescale(tmp_path, reshard_on):
    """fp8 histories/scales rescale by T_new/T_old across a pp-degree
    change (pipelined amax observations sum over T = M + P - 1 ticks)."""
    mesh = mesh_of({"dp": 8})
    hist = np.arange(12, dtype=np.float32).reshape(3, 4)
    scale = np.asarray([3.0, 6.0, 9.0], np.float32)
    st = {"fp8_meta": {"scale": {"qkv": {"x": shard(scale, mesh, P())}},
                       "amax_history": {"qkv": {"x": shard(hist, mesh,
                                                           P())}}}}
    ckpt.save_state_dict(st, str(tmp_path),
                         layout_extra={"fp8_amax_ticks": 3})
    tgt = {"fp8_meta": {
        "scale": {"qkv": {"x": shard(jnp.zeros((3,)), mesh, P())}},
        "amax_history": {"qkv": {"x": shard(jnp.zeros((3, 4)), mesh,
                                            P())}}}}
    out = ckpt.load_resharded(tgt, str(tmp_path),
                              layout_extra={"fp8_amax_ticks": 2})
    np.testing.assert_allclose(
        np.asarray(out["fp8_meta"]["amax_history"]["qkv"]["x"]),
        hist * (2.0 / 3.0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out["fp8_meta"]["scale"]["qkv"]["x"]),
        scale * (2.0 / 3.0), rtol=1e-6)


def test_fp8_ticks_only_change_is_a_mismatch(tmp_path, reshard_on):
    """A ticks-only change (e.g. num_microbatches at a fixed mesh) must
    route resume through the reshard path — the plain loader would skip
    the amax rescale and leave the quantization grids off by T_a/T_b."""
    mesh = mesh_of({"dp": 8})
    st = {"w": shard(jnp.ones((8,)), mesh, P("dp"))}
    ckpt.save_state_dict(st, str(tmp_path),
                         layout_extra={"fp8_amax_ticks": 3})
    md = ckpt.load_metadata(str(tmp_path))
    tgt = {"w": shard(jnp.zeros((8,)), mesh, P("dp"))}
    assert ckpt.layout_mismatch(md, tgt) is None  # no target ticks: plain
    mm = ckpt.layout_mismatch(md, tgt, layout_extra={"fp8_amax_ticks": 5})
    assert mm == {"fp8_amax_ticks": {"saved": 3, "target": 5}}
    assert ckpt.layout_mismatch(
        md, tgt, layout_extra={"fp8_amax_ticks": 3}) is None


def test_follow_carry_missing_keeps_template(tmp_path, reshard_on,
                                             jsonl_events):
    """Enabling fp8 at RESUME time (checkpoint saved without it): the
    'follow'-policy carry is absent from the checkpoint, so the template's
    fresh fp8_meta is kept (with an event) instead of raising KeyError."""
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"opt": {"w": shard(jnp.ones((8,)), mesh,
                                             P("dp"))}}, str(tmp_path))
    fresh = shard(jnp.full((3,), 0.125), mesh, P())
    tgt = {"opt": {"w": shard(jnp.zeros((8,)), mesh, P("dp")),
                   "fp8_meta": {"scale": {"qkv": {"x": fresh}}}}}
    out = ckpt.load_resharded(tgt, str(tmp_path))
    np.testing.assert_array_equal(
        np.asarray(out["opt"]["fp8_meta"]["scale"]["qkv"]["x"]),
        np.full((3,), 0.125))
    np.testing.assert_array_equal(np.asarray(out["opt"]["w"]), np.ones(8))
    ev = [e for e in jsonl_events() if e["event"] == "ckpt_carry_reset"]
    assert ev and ev[-1]["reason"] == "missing"


def test_unknown_carry_policy_rejected(tmp_path, reshard_on):
    """A typo'd policy name must fail loudly, not silently transfer the
    carry verbatim (the corruption the policies exist to prevent)."""
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"w": shard(jnp.ones((8,)), mesh, P("dp"))},
                         str(tmp_path))
    tgt = {"w": shard(jnp.zeros((8,)), mesh, P("dp"))}
    with pytest.raises(ValueError, match="unknown carry policy"):
        ckpt.load_resharded(tgt, str(tmp_path),
                            layout_extra={"carries": {"comm_ef": "reset"}})


def test_missing_key_without_policy_raises(tmp_path, reshard_on):
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"a": shard(jnp.ones((8,)), mesh, P("dp"))},
                         str(tmp_path))
    with pytest.raises(KeyError):
        ckpt.load_resharded({"b": shard(jnp.ones((8,)), mesh, P("dp"))},
                            str(tmp_path))


# -- mid-save faults ---------------------------------------------------------
def test_torn_chunk_crash_falls_back_to_previous_commit(tmp_path):
    """A chunk file torn mid-save (storage layer lost the tail after the
    rename) crashes the save; recovery must fall back to the previous
    committed step and never surface the torn directory."""
    d = str(tmp_path)
    good = commit_checkpoint({"w": jnp.ones((4,))}, d, 1)
    _arm("ckpt/torn_chunk:1")
    with pytest.raises(FaultInjected):
        commit_checkpoint({"w": jnp.full((4,), 2.0)}, d, 2)
    _arm("")
    # the staging straggler holds a REALLY torn file
    torn = [f for f in os.listdir(d) if f.endswith(".tmp")]
    assert torn, os.listdir(d)
    assert latest_checkpoint(d) == good          # straggler GC'd, good wins
    assert sorted(os.listdir(d)) == ["step_00000001"]
    out = ckpt.load_state_dict({"w": jnp.zeros((4,))}, good)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(4))


def test_torn_chunk_kill_sequence_never_discoverable(tmp_path):
    """Spawned hard-kill variant: no sequence of torn-chunk crashes leaves
    latest_checkpoint pointing at an unloadable directory."""
    worker = os.path.join(REPO, "tests", "resilience_worker.py")
    d = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               FLAGS_fault_inject="ckpt/torn_chunk:2:kill")
    p = subprocess.run([sys.executable, worker, "train", d],
                       env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == faults.FAULT_EXIT_CODE, (p.stdout, p.stderr)
    latest = latest_checkpoint(d)
    assert latest is not None and checkpoint_step(latest) == 3
    out = ckpt.load_state_dict(
        {"step": 0, "state": {"w": jnp.zeros((4,), jnp.float32)}}, latest)
    assert out["step"] == 3


# -- checkpoint-root hardening -----------------------------------------------
def test_latest_checkpoint_skips_foreign_entries(tmp_path, jsonl_events):
    d = str(tmp_path)
    good = commit_checkpoint({"w": jnp.ones((2,))}, d, 1)
    (tmp_path / "notes.txt").write_text("operator scratch")
    (tmp_path / "step_abc").mkdir()          # foreign dir, step-ish name
    (tmp_path / "experiment").mkdir()
    assert latest_checkpoint(d) == good
    # foreign entries are never garbage-collected
    assert (tmp_path / "notes.txt").exists()
    assert (tmp_path / "step_abc").is_dir()
    assert (tmp_path / "experiment").is_dir()
    ev = [e for e in jsonl_events()
          if e["event"] == "ckpt_root_foreign_entries"]
    assert ev and ev[-1]["count"] == 3


def test_latest_checkpoint_falls_back_past_corrupt_metadata(tmp_path,
                                                            jsonl_events):
    """A COMMITTED dir whose metadata was corrupted after the fact is
    skipped with a warning event; discovery falls back to the previous
    committed step instead of handing back an unloadable dir."""
    d = str(tmp_path)
    good = commit_checkpoint({"w": jnp.ones((2,))}, d, 1)
    bad = commit_checkpoint({"w": jnp.full((2,), 2.0)}, d, 2)
    (tmp_path / "step_00000002" / "0.metadata").write_bytes(b"garbage")
    assert latest_checkpoint(d) == good
    ev = [e for e in jsonl_events()
          if e["event"] == "ckpt_unloadable_skipped"]
    assert ev and "step_00000002" in ev[-1]["path"]
    # validate=False restores the pure marker check
    assert latest_checkpoint(d, validate=False) == bad


def test_latest_checkpoint_falls_back_past_missing_data_file(tmp_path):
    d = str(tmp_path)
    good = commit_checkpoint({"w": jnp.ones((2,))}, d, 1)
    commit_checkpoint({"w": jnp.full((2,), 2.0)}, d, 2)
    os.unlink(tmp_path / "step_00000002" / "0_0.distcp")
    assert latest_checkpoint(d) == good


def test_run_resilient_resumes_past_corrupted_newest(tmp_path):
    """End to end: corrupt the newest commit, run_resilient resumes from
    the previous one and reaches the same final state as an uninterrupted
    run."""
    def sgd(state, i):
        x = jax.random.normal(jax.random.PRNGKey(i), (4,), jnp.float32)
        return {"w": state["w"] - 0.2 * (state["w"] - x)}, float(i)

    w0 = {"w": jnp.zeros((4,), jnp.float32)}
    golden, _ = run_resilient(sgd, w0, steps=6,
                              ckpt_dir=str(tmp_path / "a"), ckpt_every=2)
    d = str(tmp_path / "b")
    run_resilient(sgd, w0, steps=4, ckpt_dir=d, ckpt_every=2)
    (tmp_path / "b" / "step_00000004" / "0.metadata").write_bytes(b"junk")
    state, info = run_resilient(sgd, w0, steps=6, ckpt_dir=d, ckpt_every=2)
    assert info["resumed_from"].endswith("step_00000002")
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.asarray(golden["w"]))


# -- resilient driver: elastic resume ----------------------------------------
def test_run_resilient_reshards_on_mesh_change(tmp_path, reshard_on,
                                               jsonl_events):
    """Kill on mesh A (8 devices), resume on mesh B (4 devices): the driver
    detects the recorded mismatch, reshards, and the trajectory is bitwise
    the B-only golden's (elementwise update — no cross-replica math)."""
    def make_step(mesh):
        spec = NamedSharding(mesh, P(mesh.axis_names[0]))

        @jax.jit
        def upd(w, x):
            return w - 0.5 * (w - x)

        def step_fn(state, i):
            x = jax.device_put(
                jax.random.normal(jax.random.PRNGKey(i), (8, 2),
                                  jnp.float32), spec)
            w = upd(state["w"], x)
            return {"w": w}, float(jnp.sum(w))
        return step_fn

    mesh_a, mesh_b = mesh_of({"dp": 8}), mesh_of({"x": 4}, n=4)
    w0 = np.zeros((8, 2), np.float32)

    golden_losses = {}
    run_resilient(make_step(mesh_b),
                  {"w": shard(w0, mesh_b, P("x"))}, steps=6,
                  ckpt_dir=str(tmp_path / "gold"), ckpt_every=0,
                  resume=False,
                  on_step=lambda i, l: golden_losses.__setitem__(i, l))

    d = str(tmp_path / "run")
    _arm("loop/before_step:4")
    with pytest.raises(FaultInjected):
        run_resilient(make_step(mesh_a),
                      {"w": shard(w0, mesh_a, P("dp"))}, steps=6,
                      ckpt_dir=d, ckpt_every=3)
    _arm("")
    losses = {}
    state, info = run_resilient(
        make_step(mesh_b), {"w": shard(w0, mesh_b, P("x"))}, steps=6,
        ckpt_dir=d, ckpt_every=3,
        on_step=lambda i, l: losses.__setitem__(i, l))
    assert info["resharded"] is True
    assert info["resumed_from"].endswith("step_00000003")
    assert state["w"].sharding.spec == P("x")
    for i in (3, 4, 5):
        assert losses[i] == golden_losses[i]  # bitwise
    assert any(e["event"] == "resilience_reshard_resume"
               for e in jsonl_events())


def test_run_resilient_flag_off_keeps_plain_load(tmp_path):
    """FLAGS_ckpt_reshard off: resume goes through the plain loader even
    when the checkpoint carries no layout — today's behavior, untouched."""
    def sgd(state, i):
        return {"w": state["w"] + 1.0}, 1.0
    d = str(tmp_path)
    run_resilient(sgd, {"w": jnp.zeros((4,))}, steps=2, ckpt_dir=d,
                  ckpt_every=2)
    state, info = run_resilient(sgd, {"w": jnp.zeros((4,))}, steps=4,
                                ckpt_dir=d, ckpt_every=2)
    assert info["resumed_from"].endswith("step_00000002")
    assert info["resharded"] is False
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full((4,), 4.0))
    md = ckpt.load_metadata(os.path.join(d, "step_00000004"))
    assert md.schema_version == 1 and md.layout is None


# -- inspect CLI -------------------------------------------------------------
def test_inspect_cli_human_and_json(tmp_path, reshard_on, capsys):
    from paddle_tpu.distributed.checkpoint.__main__ import main
    mesh = mesh_of({"dp": 2, "mp": 4})
    d = str(tmp_path)
    commit_checkpoint(
        {"step": 5, "state": {"w": shard(jnp.ones((8, 4)), mesh,
                                         P("mp", None))}},
        d, 5, layout_extra={"zero1": True, "pp": {"num_layers": 2, "pp": 1,
                                                  "vpp": 1}})
    # a commit ROOT resolves to its newest committed step
    assert main(["inspect", d]) == 0
    out = capsys.readouterr().out
    assert "schema version: 2" in out
    assert "dp2 x mp4" in out
    assert "state.w: (8, 4) float32" in out
    assert "extra.zero1: True" in out
    # --json on the step dir, with the per-file chunk map
    assert main(["inspect", os.path.join(d, "step_00000005"), "--json"]) == 0
    desc = json.loads(capsys.readouterr().out)
    assert desc["schema_version"] == 2
    assert desc["layout"]["mesh"] == {"dp": 2, "mp": 4}
    assert desc["tensors"]["state.w"]["global_shape"] == [8, 4]
    assert desc["tensors"]["state.w"]["spec"] == ["mp", None]
    assert "misc_keys" in desc and "step" in desc["misc_keys"]
    assert sum(len(v) for v in desc["files"].values()) == desc["n_chunks"]


def test_inspect_cli_v1_checkpoint(tmp_path, capsys):
    from paddle_tpu.distributed.checkpoint.__main__ import main
    mesh = mesh_of({"dp": 8})
    ckpt.save_state_dict({"w": shard(jnp.ones((8,)), mesh, P("dp"))},
                         str(tmp_path))
    assert main(["inspect", str(tmp_path), "--chunks"]) == 0
    out = capsys.readouterr().out
    assert "schema version: 1" in out and "no layout metadata" in out
    assert "0_0.distcp" in out


# -- hybrid-engine elastic legs (slow tier) ----------------------------------
def _hybrid_setup(mesh, zero1=False, fp8=False, num_microbatches=2):
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2)
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=num_microbatches, zero1_dp=zero1,
        fp8=fp8, donate=False)   # run_resilient keeps a step's input state
    params = shard_params(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    state = {"params": params, "opt": init_state(params)}
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))

    def step_fn(st, i):
        del i
        p, s, loss = step(st["params"], st["opt"], tokens, labels,
                          jnp.float32(1e-2))
        return {"params": p, "opt": s}, loss
    return step_fn, state, init_state.layout_extra


def _elastic_leg(tmp_path, dims_a, n_a, dims_b, n_b, z_a=False, z_b=False,
                 fp8=False):
    """Shared harness: golden on B uninterrupted; A killed by injection
    after the step-4 commit; resume on B. Returns (post-kill losses,
    golden losses, resume info)."""
    golden = {}
    step_fn, state, _ = _hybrid_setup(mesh_of(dims_b, n=n_b), zero1=z_b,
                                      fp8=fp8)
    run_resilient(step_fn, state, steps=6, ckpt_dir=str(tmp_path / "gold"),
                  ckpt_every=0, resume=False,
                  on_step=lambda i, l: golden.__setitem__(i, l))
    d = str(tmp_path / "run")
    step_fn, state, extra = _hybrid_setup(mesh_of(dims_a, n=n_a), zero1=z_a,
                                          fp8=fp8)
    _arm("loop/before_step:5")
    with pytest.raises(FaultInjected):
        run_resilient(step_fn, state, steps=6, ckpt_dir=d, ckpt_every=2,
                      layout_extra=extra)
    _arm("")
    assert checkpoint_step(latest_checkpoint(d)) == 4
    losses = {}
    step_fn, state, extra = _hybrid_setup(mesh_of(dims_b, n=n_b), zero1=z_b,
                                          fp8=fp8)
    _, info = run_resilient(step_fn, state, steps=6, ckpt_dir=d,
                            ckpt_every=2, layout_extra=extra,
                            on_step=lambda i, l: losses.__setitem__(i, l))
    assert info["resharded"] is True, info
    return losses, golden, info


def test_elastic_hybrid_pp_shrink_bitwise(tmp_path, reshard_on):
    """Acceptance: pp2 -> pp1 (dp/mp fixed) elastic resume is BITWISE equal
    to the uninterrupted mesh-B run — the pipeline schedule computes the
    same per-microbatch sums in the same order, and the reshard itself is
    lossless."""
    losses, golden, _ = _elastic_leg(
        tmp_path, {"dp": 1, "pp": 2, "mp": 2}, 4,
        {"dp": 1, "pp": 1, "mp": 2}, 2)
    assert {i: losses[i] for i in (4, 5)} == {i: golden[i] for i in (4, 5)}


def test_elastic_hybrid_zero1_on_to_off_bitwise(tmp_path, reshard_on):
    """Acceptance: zero1 on -> off at fixed dp is bitwise — the per-shard
    Adam update is elementwise and psum_scatter/dp adds the same terms as
    pmean."""
    losses, golden, _ = _elastic_leg(
        tmp_path, {"dp": 4, "pp": 1, "mp": 2}, 8,
        {"dp": 4, "pp": 1, "mp": 2}, 8, z_a=True, z_b=False)
    assert {i: losses[i] for i in (4, 5)} == {i: golden[i] for i in (4, 5)}


def test_elastic_hybrid_issue_pair_dp_regroup(tmp_path, reshard_on):
    """The ISSUE pair dp2·pp2·mp2 -> dp4·pp2·mp1: dp/mp regrouping
    reassociates the gradient reductions, so parity is ulp-level
    (measured ~5e-7 fp32), not bitwise."""
    losses, golden, _ = _elastic_leg(
        tmp_path, {"dp": 2, "pp": 2, "mp": 2}, 8,
        {"dp": 4, "pp": 2, "mp": 1}, 8)
    for i in (4, 5):
        assert abs(losses[i] - golden[i]) < 5e-5, (i, losses[i], golden[i])


def test_elastic_hybrid_fp8_carries_rescaled(tmp_path, reshard_on):
    """fp8 elastic resume across a pp change: the carried amax histories
    rescale by T_new/T_old (verified exactly against the checkpoint), and
    training continues on the new mesh tracking the golden closely (the
    pre-kill steps quantized on mesh A's grids, so bitwise is out of scope
    by construction)."""
    losses, golden, info = _elastic_leg(
        tmp_path, {"dp": 2, "pp": 2, "mp": 1}, 4,
        {"dp": 2, "pp": 1, "mp": 1}, 2, fp8=True)
    for i in (4, 5):
        assert abs(losses[i] - golden[i]) < 5e-2, (i, losses[i], golden[i])
    # exactness of the tick rescale: loaded history == saved x (T_b/T_a)
    ck = info["resumed_from"]
    saved = ckpt.load_full_state_dict(ck)
    hist_saved = saved["state"]["opt"]["fp8_meta"]["amax_history"]
    md = ckpt.load_metadata(ck)
    t_src = md.layout.extra["fp8_amax_ticks"]
    assert t_src == 2 + 2 - 1
    mesh_b = mesh_of({"dp": 2, "pp": 1, "mp": 1}, n=2)
    _, state_b, extra_b = _hybrid_setup(mesh_b, fp8=True)
    t_dst = extra_b["fp8_amax_ticks"]
    assert t_dst == 2 + 1 - 1
    out = ckpt.load_resharded(
        {"step": 0, "state": state_b}, ck, layout_extra=extra_b)
    hist_loaded = out["state"]["opt"]["fp8_meta"]["amax_history"]
    for site in hist_saved:
        np.testing.assert_allclose(
            np.asarray(hist_loaded[site]["w"]),
            np.asarray(hist_saved[site]["w"]) * (t_dst / t_src),
            rtol=1e-6, err_msg=site)


def test_reshard_1b_checkpoint_throughput(tmp_path):
    """Satellite (VERDICT weak #6): realistic-shape reshard — save the
    FULL hybrid train state (params + zero1-sharded Adam moments +
    telemetry carry) of a ~1.1B-param GPT on dp2·pp2·mp2 with the
    interleaved vpp=2 block layout through the ASYNC crash-safe commit,
    then reshard-load it onto dp4·pp2·mp1 / vpp=1 / zero1-OFF — mesh
    regroup, zero1 toggle, pp-adaptor block permutation and carry
    policies all at once, on hundreds of on-disk chunks. Prints the
    host-disk MB/s (not a device metric)."""
    import time
    from paddle_tpu.models import gpt as G

    paddle.set_flags({"FLAGS_ckpt_reshard": True, "FLAGS_telemetry": True})
    cfg = G.GPTConfig(vocab_size=50304, hidden_size=1792, num_layers=24,
                      num_heads=16, max_seq_len=512, dtype=jnp.float32)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)

    mesh_a = mesh_of({"dp": 2, "pp": 2, "mp": 2})
    _, shard_a, init_a = G.build_hybrid_train_step(
        cfg, mesh_a, opt, num_microbatches=2, virtual_pp=2, zero1_dp=True)
    host_params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(host_params))
    assert n_params > 1.0e9, n_params
    params_a = shard_a(host_params)
    state_a = {"params": params_a, "opt": init_a(params_a)}
    n_leaves = len(jax.tree.leaves(state_a))

    root = str(tmp_path / "ck")
    t0 = time.monotonic()
    path = commit_checkpoint({"step": 0, "state": state_a}, root, 0,
                             async_save=True,
                             layout_extra=init_a.layout_extra)
    save_s = time.monotonic() - t0
    total_mb = sum(os.path.getsize(os.path.join(path, f))
                   for f in os.listdir(path)) / 1e6
    md = ckpt.load_metadata(path)
    n_chunks = sum(len(v) for v in md.state_dict_metadata.values())
    assert md.schema_version == 2
    assert md.layout.mesh == {"dp": 2, "pp": 2, "mp": 2}
    assert n_chunks >= 150, n_chunks  # a real many-piece reshard
    del state_a, params_a  # free mesh-A buffers before the B-side load

    mesh_b = mesh_of({"dp": 4, "pp": 2, "mp": 1})
    _, shard_b, init_b = G.build_hybrid_train_step(
        cfg, mesh_b, opt, num_microbatches=2, virtual_pp=1, zero1_dp=False)
    params_b = shard_b(jax.tree.map(jnp.zeros_like, host_params))
    state_b = {"params": params_b, "opt": init_b(params_b)}
    template = {"step": 0, "state": state_b}
    mm = ckpt.layout_mismatch(md, template,
                              layout_extra=init_b.layout_extra)
    assert mm and "mesh" in mm and mm.get("pp_relayout") is True
    t0 = time.monotonic()
    out = ckpt.load_resharded(template, path, metadata=md,
                              layout_extra=init_b.layout_extra)
    load_s = time.monotonic() - t0

    # vpp2 chunk-major storage came back in canonical order
    got = out["state"]["params"]["blocks"]["qkv_w"]
    want = host_params["blocks"]["qkv_w"]
    for layer in (0, 7, 23):
        np.testing.assert_array_equal(np.asarray(got[layer, :4, :4]),
                                      np.asarray(want[layer, :4, :4]))
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]
                                             ["head_w"][:8, :8]),
                                  np.asarray(host_params["head_w"][:8, :8]))
    # zero1-sharded moments arrived replicated (zero1 off) and zeroed
    m1 = out["state"]["opt"]["opt"]["slots"]["blocks"]["qkv_w"]["moment1"]
    assert tuple(m1.shape) == tuple(want.shape)
    assert float(jnp.max(jnp.abs(m1[0, :64, :64]))) == 0.0
    print(f"\n[1b-reshard] params={n_params / 1e9:.2f}B leaves={n_leaves} "
          f"chunks={n_chunks} bytes={total_mb:.0f}MB "
          f"save={save_s:.1f}s ({total_mb / save_s:.0f} MB/s) "
          f"reshard-load={load_s:.1f}s ({total_mb / load_s:.0f} MB/s)")


def test_two_process_elastic_restart(tmp_path):
    """Satellite: 2-process dp2 mesh hard-killed mid-run, resumed on a
    1-process half-size mesh with reshard-on-load, loss parity vs the
    uninterrupted mesh-B golden (SKIPs where the jaxlib CPU backend lacks
    multiprocess collectives, like the other spawn legs)."""
    from paddle_tpu.distributed import mp_smoke
    try:
        res = mp_smoke.elastic_restart_check(8, str(tmp_path / "ck"),
                                             devices=jax.devices())
    except mp_smoke.ClusterUnsupported as e:
        pytest.skip(f"mp spawn unsupported on this platform: {e}")
    assert res["resharded"] is True
    assert res["killed_at_step"] == 3
    assert res["max_loss_diff"] <= 5e-5
