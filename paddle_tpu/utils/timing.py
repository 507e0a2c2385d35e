"""Host-side timing probes for the smoke and the benches."""

from __future__ import annotations

import time

_RTT_S = None


def dispatch_rtt_s() -> float:
    """Measured dispatch + scalar-fetch round trip on the default
    device, cached for the process: what a synchronous host step pays
    per dispatch whatever the program does."""
    global _RTT_S
    if _RTT_S is None:
        import jax.numpy as jnp
        x = jnp.zeros(())
        float(x + 1)  # warm the dispatch path
        t0 = time.perf_counter()
        float(x + 2)
        _RTT_S = time.perf_counter() - t0
    return _RTT_S
