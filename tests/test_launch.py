"""Launcher tests (reference pattern: subprocess-spawn with env rendezvous,
test_dist_base.py:954; elastic restart fleet/elastic)."""

import json
import os
import sys
import textwrap

import pytest

from paddle_tpu import _native
from paddle_tpu.distributed.launch import (CollectiveController, Context,
                                           launch)
from paddle_tpu.distributed.launch.elastic import ElasticManager
from paddle_tpu.distributed.store import TCPStore

NATIVE = _native.load() is not None
pytestmark = pytest.mark.skipif(not NATIVE, reason="native build unavailable")


def test_launch_two_workers_env(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        out = sys.argv[1]
        info = {k: os.environ.get(k) for k in
                ["PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                 "PADDLE_TRAINER_ENDPOINTS", "PADDLE_LOCAL_RANK",
                 "JAX_PROCESS_ID", "JAX_NUM_PROCESSES"]}
        with open(os.path.join(out, "rank_%s.json" %
                               os.environ["PADDLE_TRAINER_ID"]), "w") as f:
            json.dump(info, f)
    """))
    rc = launch(["--nproc_per_node", "2", "--log_dir",
                 str(tmp_path / "log"), str(script), str(tmp_path)])
    assert rc == 0
    for r in range(2):
        info = json.load(open(tmp_path / f"rank_{r}.json"))
        assert info["PADDLE_TRAINER_ID"] == str(r)
        assert info["PADDLE_TRAINERS_NUM"] == "2"
        assert info["JAX_PROCESS_ID"] == str(r)
        assert len(info["PADDLE_TRAINER_ENDPOINTS"].split(",")) == 2


def test_launch_propagates_failure(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(7)\n")
    rc = launch(["--nproc_per_node", "1", "--log_dir",
                 str(tmp_path / "log"), str(script)])
    assert rc == 7


def test_elastic_restart_recovers(tmp_path):
    """Worker fails on first attempt (marker file absent), succeeds on the
    restart — elastic_level 1 must retry and exit 0."""
    script = tmp_path / "flaky.py"
    marker = tmp_path / "attempted"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        marker = {str(marker)!r}
        if not os.path.exists(marker):
            open(marker, "w").write("1")
            sys.exit(101)
        sys.exit(0)
    """))
    rc = launch(["--nproc_per_node", "1", "--elastic_level", "1",
                 "--max_restarts", "2", "--log_dir", str(tmp_path / "log"),
                 str(script)])
    assert rc == 0
    assert marker.exists()


def test_elastic_level0_no_restart(tmp_path):
    script = tmp_path / "flaky.py"
    marker = tmp_path / "attempted"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        marker = {str(marker)!r}
        if not os.path.exists(marker):
            open(marker, "w").write("1")
            sys.exit(101)
        sys.exit(0)
    """))
    rc = launch(["--nproc_per_node", "1", "--log_dir",
                 str(tmp_path / "log"), str(script)])
    assert rc == 101


def test_hung_worker_detected_via_heartbeat(tmp_path):
    """A worker that registers a heartbeat then deadlocks must be detected
    and the job failed (level 0 → exit ELASTIC_EXIT_CODE=101)."""
    script = tmp_path / "hang.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, %r)
        from paddle_tpu.distributed.launch.elastic import worker_heartbeat
        em = worker_heartbeat(interval=0.1)
        em.interval = 0.1
        time.sleep(0.5)   # heartbeat alive...
        em.stop()         # ...then the 'hang': beats stop, process lives
        time.sleep(60)
    """ % os.getcwd()))
    import time
    t0 = time.time()
    rc = launch(["--nproc_per_node", "1", "--elastic_level", "1",
                 "--max_restarts", "0", "--log_dir", str(tmp_path / "log"),
                 str(script)])
    assert rc == 101
    assert time.time() - t0 < 40, "hang was not detected promptly"


def test_finished_rank_not_judged_hung(tmp_path):
    """Rank 0 exits cleanly early; rank 1 keeps training past rank 0's
    heartbeat staleness. The job must still succeed — finished ranks are
    excluded from hang detection."""
    script = tmp_path / "uneven.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, %r)
        from paddle_tpu.distributed.launch.elastic import worker_heartbeat
        em = worker_heartbeat(interval=0.2)
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        if rank == 0:
            sys.exit(0)         # finishes immediately; hb goes stale
        time.sleep(7)           # > heartbeat_timeout while rank 0 is stale
        em.stop()
        sys.exit(0)
    """ % os.getcwd()))
    rc = launch(["--nproc_per_node", "2", "--elastic_level", "1",
                 "--max_restarts", "0", "--log_dir", str(tmp_path / "log"),
                 str(script)])
    assert rc == 0


def test_elastic_manager_heartbeats():
    store = TCPStore("127.0.0.1", 0, world_size=1, is_master=True)
    em = ElasticManager(store, "job1", np=2, heartbeat_interval=0.1,
                        heartbeat_timeout=0.5)
    em.register(0)
    em.start_heartbeat(0)
    import time
    time.sleep(0.3)
    assert 0 not in em.dead_members()
    assert 1 in em.dead_members()  # never heartbeated
    em.stop()
    time.sleep(0.7)
    assert 0 in em.dead_members()  # heartbeat went stale after stop
    assert em.desired_np() == 2
    em.set_desired_np(3)
    assert em.desired_np() == 3 and em.need_rescale()
    store.close()


def test_restart_count_env_increments(tmp_path):
    """Workers see PADDLE_RESTART_COUNT so they can auto-resume from a
    checkpoint after an elastic restart."""
    script = tmp_path / "counting.py"
    marker = tmp_path / "attempted"
    out = tmp_path / "counts.txt"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        with open({str(out)!r}, "a") as f:
            f.write(os.environ["PADDLE_RESTART_COUNT"] + "\\n")
        marker = {str(marker)!r}
        if not os.path.exists(marker):
            open(marker, "w").write("1")
            sys.exit(101)
        sys.exit(0)
    """))
    rc = launch(["--nproc_per_node", "1", "--elastic_level", "1",
                 "--max_restarts", "2", "--log_dir", str(tmp_path / "log"),
                 str(script)])
    assert rc == 0
    counts = out.read_text().split()
    assert counts == ["0", "1"]


def test_elastic_scale_out(tmp_path, monkeypatch):
    """Scale in/out (VERDICT r1 #8): changing the desired world size on
    the store rebuilds the pod at the new size. The pod starts at np=1;
    the worker itself requests np=2 on its first incarnation, then both
    ranks of the rebuilt pod write markers."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        out = sys.argv[1]
        n = os.environ["PADDLE_TRAINERS_NUM"]
        r = os.environ["PADDLE_TRAINER_ID"]
        open(os.path.join(out, f"seen_np{n}_r{r}"), "w").write("1")
        if n == "1":
            # request a scale-out from inside the job, then idle so the
            # controller (not our exit) drives the rebuild
            from paddle_tpu.distributed.launch import scale_job
            ep = os.environ["PADDLE_ELASTIC_STORE_ENDPOINT"]
            scale_job(ep, os.environ["PADDLE_JOB_ID"], 2)
            time.sleep(30)
    """))
    import os as _os
    monkeypatch.setenv("PYTHONPATH", _os.pathsep.join(
        filter(None, ["/root/repo", _os.environ.get("PYTHONPATH")])))
    rc = launch(["--nproc_per_node", "1", "--elastic_level", "1",
                 "--max_restarts", "2", "--job_id", "scaletest",
                 "--log_dir", str(tmp_path / "log"), str(script),
                 str(tmp_path)])
    assert rc == 0
    assert (tmp_path / "seen_np1_r0").exists()
    assert (tmp_path / "seen_np2_r0").exists()
    assert (tmp_path / "seen_np2_r1").exists()


def test_auto_tune_picks_best_and_runs_real_job(tmp_path, monkeypatch):
    """--auto_tune trials the user's script over PlanCandidates (the
    planner vocabulary — JSON env protocol) and the real run sees the
    winner (reference launch/main.py auto-tuner mode)."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        from paddle_tpu.distributed.launch.auto_tune import (
            candidate_from_env, is_trial, report_metric)
        cand = candidate_from_env()
        if is_trial():
            # fake benchmark: prefer high mp, then micro_batches
            report_metric(cand.mp * 100 + cand.micro_batches)
        else:
            with open(os.path.join(sys.argv[1], "final.txt"), "w") as f:
                f.write(os.environ["PADDLE_AUTO_TUNER_CANDIDATE"])
    """))
    cfg = tmp_path / "tune.json"
    cfg.write_text(json.dumps({
        "global_batch": 4, "num_layers": 4, "num_heads": 4,
        "hidden_size": 32, "vocab_size": 64, "seq_len": 16,
        "micro_batch_options": [1, 2], "top_k": 8,
    }))
    import os as _os
    monkeypatch.setenv("PYTHONPATH", _os.pathsep.join(
        filter(None, ["/root/repo", _os.environ.get("PYTHONPATH")])))
    rc = launch(["--nproc_per_node", "1", "--auto_tune",
                 "--auto_tuner_json", str(cfg), "--job_id", "tunetest",
                 "--log_dir", str(tmp_path / "log"), str(script),
                 str(tmp_path)])
    assert rc == 0
    final = json.loads((tmp_path / "final.txt").read_text())
    # world=1 -> dp=mp=pp=ep=1; best micro_batches=2 by the metric
    assert (final["dp"], final["mp"], final["pp"], final["ep"]) == \
        (1, 1, 1, 1)
    assert final["micro_batches"] == 2, final


def test_several_processes_per_tpu_host_refused(tmp_path, monkeypatch):
    """Nothing confines a launched worker to one chip, and a chip belongs
    to one process: on a TPU host --nproc_per_node > 1 is refused with a
    pointer to the one-process mesh, before any worker starts. The host
    check never touches jax (the launcher must not load the TPU
    library)."""
    from paddle_tpu.distributed.launch import controllers
    script = tmp_path / "train.py"
    script.write_text("open(__import__('sys').argv[1] + '/ran', 'w')\n")
    monkeypatch.setattr(controllers, "_tpu_host", lambda envs: True)
    with pytest.raises(RuntimeError, match="one process"):
        launch(["--nproc_per_node", "2", "--log_dir",
                str(tmp_path / "log"), str(script), str(tmp_path)])
    assert not (tmp_path / "ran").exists()
    # one process per host stays allowed there
    rc = launch(["--nproc_per_node", "1", "--log_dir",
                 str(tmp_path / "log"), str(script), str(tmp_path)])
    assert rc == 0 and (tmp_path / "ran").exists()


def test_tpu_host_detection_is_jax_free(monkeypatch):
    import glob
    from paddle_tpu.distributed.launch import controllers
    monkeypatch.setattr(glob, "glob",
                        lambda pat: ["/dev/accel0"] if "accel" in pat else [])
    assert controllers._tpu_host({}) is True
    assert controllers._tpu_host({"JAX_PLATFORMS": "cpu"}) is False
    monkeypatch.setattr(glob, "glob", lambda pat: [])
    assert controllers._tpu_host({}) is False
