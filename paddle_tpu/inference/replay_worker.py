"""Spawned worker half of the serving kill-and-replay leg (ISSUE 13;
launcher half in ``inference.resilient.kill_replay_check``, used by
tests/test_serving_resilience.py and the ``__graft_entry__`` dryrun).

Runs a small deterministic greedy serving workload under
``run_serving_resilient`` with a disk journal, so the parent can
hard-kill it (an armed ``serving/step:N:kill`` fault in the environment),
respawn it onto the same journal, and assert the resumed outputs are
bitwise-identical to an uninterrupted run with exactly-once token
delivery and zero leaked KV pages.

Usage: ``python -m paddle_tpu.inference.replay_worker <workdir>``. Crash
points come from ``FLAGS_fault_inject`` in the environment. Prints one
``RESULT {json}`` line: per-request outputs, the tokens delivered by
THIS process, final pool accounting, statuses and rebuild count.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def workload():
    """Deterministic workload shared by every spawn: tiny GPT, 4 mixed
    greedy requests — outputs are a pure function of the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import gpt as G

    cfg = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=128, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,))
               for n in (9, 13, 6, 11)]
    news = [6, 4, 7, 5]
    return cfg, params, prompts, news


def main(argv):
    workdir = argv[1]
    from paddle_tpu.inference.resilient import run_serving_resilient
    from paddle_tpu.inference.serving import ServingEngine

    cfg, params, prompts, news = workload()

    def make_engine():
        return ServingEngine(params, cfg, max_batch=2, block_size=8,
                             num_blocks=24, max_blocks_per_seq=8, chunk=8,
                             adaptive_mix=False)

    delivered_here = {i: [] for i in range(len(prompts))}

    def on_token(lid, tok):
        delivered_here[lid].append(int(tok))

    reqs = [{"prompt": p, "max_new_tokens": n, "on_token": on_token}
            for p, n in zip(prompts, news)]
    results, info = run_serving_resilient(
        make_engine, reqs,
        journal_path=os.path.join(workdir, "journal.jsonl"))
    print("RESULT " + json.dumps({
        "outputs": results,
        "delivered": delivered_here,
        "free_blocks": info.get("free_blocks"),
        "pool_blocks": info.get("pool_blocks"),
        "rebuilds": info["rebuilds"],
        "statuses": info["statuses"],
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv)
