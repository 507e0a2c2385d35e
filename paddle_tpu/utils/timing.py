"""Host-side timing probes shared by the serving scheduler and benches."""

from __future__ import annotations

import time

_RTT_S = None


def dispatch_rtt_s() -> float:
    """Measured dispatch + scalar-fetch round trip on the default
    device, cached for the process — the number that decides whether
    chatty scheduling strategies (adaptive decode bursts, per-step
    fetches) pay for themselves."""
    global _RTT_S
    if _RTT_S is None:
        import jax.numpy as jnp
        x = jnp.zeros(())
        float(x + 1)  # warm the dispatch path
        t0 = time.perf_counter()
        float(x + 2)
        _RTT_S = time.perf_counter() - t0
    return _RTT_S
