"""RNG state management.

TPU-native redesign of the reference's RNG stack (reference:
paddle/phi/core/generator.{h,cc} per-device Generator;
python/paddle/distributed/fleet/layers/mpu/random.py:34 RNGStatesTracker).

Instead of stateful curand generators, we use JAX threefry key splitting:
a global Generator holds a key and deterministically splits per request.
Inside a jitted function, layers pull keys from an explicit `rng_guard`
context so the trace stays functional (keys are traced values, the Python
context only exists at trace time). The tracker keeps named streams so
tensor-parallel ranks can have distinct ("local") or identical ("global")
streams — the exact contract of RNGStatesTracker.model_parallel_random_seed.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from .enforce import AlreadyExistsError, NotFoundError
import numpy as np

__all__ = [
    "seed", "get_rng_state", "set_rng_state", "Generator", "default_generator",
    "rng_guard", "next_key", "next_mask_key", "RNGStatesTracker",
    "get_rng_state_tracker",
    "model_parallel_random_seed",
]


class Generator:
    """Splittable RNG stream. Thread-safe; deterministic given the seed."""

    def __init__(self, seed_: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed_)

    def manual_seed(self, seed_: int) -> "Generator":
        with self._lock:
            self._seed = int(seed_)
            self._count = 0
        return self

    def next_key(self) -> jax.Array:
        with self._lock:
            c = self._count
            self._count += 1
        return jax.random.fold_in(jax.random.PRNGKey(self._seed), c)

    def get_state(self):
        with self._lock:
            return {"seed": self._seed, "count": self._count}

    def set_state(self, state):
        with self._lock:
            self._seed = int(state["seed"])
            self._count = int(state["count"])


default_generator = Generator(0)


def seed(s: int) -> Generator:
    """paddle.seed equivalent: reset the global generator."""
    return default_generator.manual_seed(s)


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)


# ---------------------------------------------------------------------------
# Functional key threading for jitted forward passes.
# ---------------------------------------------------------------------------
class _KeyCtx(threading.local):
    def __init__(self):
        self.stack: List[List] = []  # each entry: [key, counter]


_ctx = _KeyCtx()


@contextlib.contextmanager
def rng_guard(key: Optional[jax.Array] = None):
    """Provide an explicit RNG key to layers executed in this scope.

    Used inside jitted train steps: ``with rng_guard(step_key): loss = model(x)``.
    Each `next_key()` call folds a fresh counter into the scope key, so layer
    call order determines streams deterministically at trace time.
    """
    if key is None:
        key = default_generator.next_key()
    _ctx.stack.append([key, 0])
    try:
        yield
    finally:
        _ctx.stack.pop()


def next_key() -> jax.Array:
    """Next RNG key: from the innermost rng_guard if active, else global."""
    if _ctx.stack:
        entry = _ctx.stack[-1]
        k = jax.random.fold_in(entry[0], entry[1])
        entry[1] += 1
        return k
    return default_generator.next_key()


def next_mask_key() -> jax.Array:
    """Key for BULK mask generation (dropout): the threefry stream seeds an
    rbg key (XLA's hardware RngBitGenerator). Threefry costs ~10 ALU ops per
    random element, at every one of a BERT-base step's ~36 dropout
    sites, while rbg bits come from the hardware generator. Key
    uniqueness/determinism still come from the threefry sequence; only the
    bit expansion changes engine."""
    k = next_key()
    from .flags import flag
    if not flag("dropout_use_rbg"):
        return k
    kd = jax.random.key_data(k).astype(jnp.uint32).reshape(-1)  # (2,)
    try:
        return jax.random.wrap_key_data(jnp.concatenate([kd, kd]),
                                        impl="rbg")
    except Exception:  # backend without rbg: keep the threefry key
        return k


# ---------------------------------------------------------------------------
# Tensor-parallel RNG tracker (reference: mpu/random.py RNGStatesTracker).
# ---------------------------------------------------------------------------
MODEL_PARALLEL_RNG = "model_parallel_rng"


class RNGStatesTracker:
    """Named RNG streams. 'global' stream is shared across TP ranks (e.g.
    residual dropout must match); the model-parallel stream differs per rank
    (e.g. dropout inside a column-parallel region)."""

    def __init__(self):
        self.states_: Dict[str, Generator] = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def add(self, name: str, seed_: int):
        if seed_ in self.seeds_:
            raise AlreadyExistsError(f"seed {seed_} already exists",
                                     op="RNGStatesTracker.add")
        if name in self.states_:
            raise AlreadyExistsError(f"state {name} already exists",
                                     op="RNGStatesTracker.add")
        self.seeds_.add(seed_)
        self.states_[name] = Generator(seed_)

    def get_states_tracker(self):
        return {n: g.get_state() for n, g in self.states_.items()}

    def set_states_tracker(self, states):
        for n, s in states.items():
            self.states_.setdefault(n, Generator(0)).set_state(s)

    @contextlib.contextmanager
    def rng_state(self, name: str = MODEL_PARALLEL_RNG):
        if name not in self.states_:
            raise NotFoundError(f"state {name} does not exist",
                                op="RNGStatesTracker.rng_state")
        with rng_guard(self.states_[name].next_key()):
            yield


_RNG_STATE_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _RNG_STATE_TRACKER


def model_parallel_random_seed(seed_: Optional[int] = None, mp_rank: int = 0):
    """Set up distinct local / identical global seeds across TP ranks
    (reference: mpu/random.py:103)."""
    base = seed_ if seed_ is not None else np.random.randint(0, 2**31 - 1)
    local_seed = base + 1024 + mp_rank
    global_seed = base
    tracker = get_rng_state_tracker()
    tracker.reset()
    tracker.add(MODEL_PARALLEL_RNG, local_seed)
    default_generator.manual_seed(global_seed)
