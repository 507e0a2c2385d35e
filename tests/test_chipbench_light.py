"""The benchmark's light tests, inside the gate.

`chipbench/tests/` is the benchmark's own suite and the tier-1 command
collects `tests/` only, so a change to what the benchmark's runners call
(the serving engine's constructor, the span and kernel names the readers
look for) could break how a cell finds its files and names unseen. The
four modules re-exported here hold the cells to their files, names,
traffic and yardstick, and take seconds together; the heavy ones
(`test_rehearsal.py`, `test_falcon_h1.py`, `test_reference.py`: every
runner end to end at toy size) stay where they are: ROADMAP Queue 3.
Nothing of `chipbench/tests/conftest.py` is needed: `tests/conftest.py`
already holds JAX to the CPU, and the repository's root is importable.
"""

import importlib

import pytest

_LIGHT = ("test_spec", "test_traffic", "test_yardstick", "test_trace_reduce")
pytest.register_assert_rewrite(*(f"chipbench.tests.{m}" for m in _LIGHT))

from chipbench.tests.test_spec import *          # noqa: E402,F401,F403
from chipbench.tests.test_traffic import *       # noqa: E402,F401,F403
from chipbench.tests.test_yardstick import *     # noqa: E402,F401,F403
from chipbench.tests.test_trace_reduce import *  # noqa: E402,F401,F403


def test_every_light_test_is_collected_here():
    """A star import lets a later module's name hide an earlier one's:
    every test of the four modules must be THE function of that name in
    this module."""
    seen = {}
    for m in _LIGHT:
        mod = importlib.import_module(f"chipbench.tests.{m}")
        for name, obj in vars(mod).items():
            if name.startswith("test_") and callable(obj):
                assert name not in seen, (name, m, seen[name])
                seen[name] = m
                assert globals()[name] is obj, (name, m)
    assert len(seen) >= 25, sorted(seen)
