"""Spawned worker half of the multi-replica router (ISSUE 16; the
launcher half is ``inference.router.SpawnedReplica``).

One worker = one serving replica in its own process, driven over a tiny
file protocol under its replica dir:

* ``inbox.<gen>.jsonl``  — the router appends request lines
  (``{"lid", "prompt", "max_new_tokens", ...}``) and finally a
  ``{"close": true}`` sentinel; the worker tail-reads complete lines.
  The generation is baked into the filename: a respawned worker reads a
  FRESH inbox, never the dead generation's (whose in-flight work the
  router already replayed onto survivors — re-reading it would
  double-deliver).
* ``journal.jsonl``      — this worker's :class:`ServingJournal` and the
  delivery channel: every sampled token is journaled (flushed, optionally
  fsynced per ``FLAGS_serving_journal_fsync``) BEFORE the router can
  observe it, and terminal statuses ride the same file. The SAME journal
  path survives respawns — the PR 13 successor-resume contract.
* ``health.json``        — heartbeat, atomically replaced every loop
  iteration; the router treats staleness as death.

SIGTERM drains: stop admission, finish in-flight within
``FLAGS_preempt_grace_s``, cancel the rest (journal marks ``requeued`` —
the router's failover replays them). Crash points come from
``FLAGS_fault_inject`` in the environment (``serving/step:3:kill`` is
the spawn-leg acceptance kill). Exits printing one ``RESULT {json}``
line: pool accounting (the zero-leak gate), per-lid delivery counts and
statuses.

Usage: ``python -m paddle_tpu.inference.router_worker <rdir> --gen N``.
"""

import json
import os
import signal
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_TERM = {"flag": False}


def _write_health(rdir: str, state: str) -> None:
    tmp = os.path.join(rdir, "health.json.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"state": state, "ts": time.time(),
                   "pid": os.getpid()}, f)
    os.replace(tmp, os.path.join(rdir, "health.json"))  # never torn


def main(argv):
    rdir = argv[1]
    gen = 1
    if "--gen" in argv:
        gen = int(argv[argv.index("--gen") + 1])

    import numpy as np
    from paddle_tpu.flags import flag
    from paddle_tpu.inference.resilient import ServingJournal
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.replay_worker import workload

    signal.signal(signal.SIGTERM,
                  lambda *_: _TERM.__setitem__("flag", True))

    cfg, params, _prompts, _news = workload()  # model only; work = inbox
    # decode_burst=2: several engine steps per request, so an armed
    # serving/step:N:kill lands mid-generation with tokens already
    # journaled (the spawn-leg acceptance needs a real partial prefix)
    eng = ServingEngine(params, cfg, max_batch=2, block_size=8,
                        num_blocks=24, max_blocks_per_seq=8, chunk=8,
                        decode_burst=2, adaptive_mix=False)
    journal = ServingJournal(os.path.join(rdir, "journal.jsonl"))
    delivered = {}

    def deliver(lid, tok):
        # journal-first IS the delivery: the router only ever sees a
        # token after this line is on disk
        journal.append(lid, int(tok))
        delivered[lid] = delivered.get(lid, 0) + 1

    inbox_path = os.path.join(rdir, f"inbox.{gen}.jsonl")
    t0 = time.monotonic()
    while not os.path.exists(inbox_path):
        if time.monotonic() - t0 > 60.0:
            sys.exit(3)
        time.sleep(0.01)
    fin = open(inbox_path, "r", encoding="utf-8")
    buf = ""
    rid_map = {}
    statuses = {}
    closing = False
    draining = False
    drain_deadline = None
    hard_deadline = time.monotonic() + 600.0
    _write_health(rdir, "ready")
    try:
        while True:
            # drain new complete inbox lines (the tail may be mid-write)
            buf += fin.read()
            lines = buf.split("\n")
            buf = lines.pop()
            for line in lines:
                if not line.strip():
                    continue
                rec = json.loads(line)
                if rec.get("close"):
                    closing = True
                    continue
                lid = int(rec["lid"])
                rid = eng.add_request(
                    np.asarray(rec["prompt"], np.int32),
                    int(rec["max_new_tokens"]),
                    float(rec.get("temperature") or 0.0),
                    rec.get("eos_id"),
                    on_token=(lambda r, t, lid=lid: deliver(lid, t)),
                    deadline_s=rec.get("deadline_s"))
                rid_map[rid] = lid
            if _TERM["flag"] and not draining:
                draining = closing = True
                drain_deadline = (time.monotonic()
                                  + float(flag("preempt_grace_s")))
                eng.drain()
                for r in eng.shed_queue("sigterm"):
                    lid = rid_map.get(r.rid)
                    if lid is not None:
                        journal.mark(lid, "requeued")
            if drain_deadline is not None and \
                    time.monotonic() > drain_deadline:
                for r in eng.cancel_all("drain_deadline"):
                    lid = rid_map.get(r.rid)
                    if lid is not None and lid not in statuses:
                        journal.mark(lid, "requeued")
                        statuses[lid] = "requeued"
                break
            if eng.has_work():
                for r in eng.step():
                    lid = rid_map.get(r.rid)
                    if lid is None or lid in statuses:
                        continue
                    st = "done" if r.status == "ok" else r.status
                    statuses[lid] = st
                    journal.mark(lid, st)
            elif closing:
                break
            else:
                time.sleep(0.01)
            _write_health(rdir, "draining" if draining else "ready")
            if time.monotonic() > hard_deadline:
                sys.exit(3)
    finally:
        journal.close()
    _write_health(rdir, "draining")
    print("RESULT " + json.dumps({
        "gen": gen,
        # free_pages(): cached-free prefix pages count as free — the
        # router's zero-leak assert reads this field
        "free_blocks": eng.free_pages(),
        "pool_blocks": eng._num_blocks - 1,
        "engine_steps": eng.engine_steps,
        "delivered": delivered,
        "statuses": statuses,
        "drained": draining,
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv)
