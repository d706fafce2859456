"""Test configuration: force an 8-device virtual CPU mesh before JAX loads.

This is the TPU-world analogue of "test multi-node without a cluster"
(SURVEY.md §4.3): sharding specs, TP decode and collective layouts are
exercised on 8 virtual CPU devices with Pallas kernels in interpret mode;
execution on the chip is covered by ``chip_smoke.py``.

The arming recipe (env flags + jax config + backend reset when a backend
was already initialised) lives in one place —
``__graft_entry__._force_virtual_cpu`` — shared with the driver's
multichip dryrun so the two can't drift.
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")

from __graft_entry__ import _force_virtual_cpu  # noqa: E402

_force_virtual_cpu(8)

import jax  # noqa: E402

assert jax.default_backend() == "cpu", "tests must run on CPU"
assert len(jax.devices()) == 8, "tests expect an 8-device virtual CPU mesh"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 (-m 'not slow')"
    )
