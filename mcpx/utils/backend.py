"""Backend set-up shared by every entry point: the n-device virtual CPU
platform that tests and host-side tools run on, and where JAX's persistent
compilation cache lives.

``force_virtual_cpu`` is the one arming recipe, shared by
``tests/conftest.py``, ``__graft_entry__.dryrun_multichip`` and the CLI's
``--platform cpu`` flags, so the three can't drift. Host-side work (planner
training, corpus building, offline evals) runs there: a chip belongs to one
process at a time, and such a process must not take it from a server.
"""

from __future__ import annotations

import os
from typing import Optional

# The cache directory is part of every cache key's context, so it must not
# move between runs: one fixed path inside the checkout, resolved from the
# package location (never from ~, a temp name, a pid or a time).
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for this process and return
    the directory in effect (None = no cache).

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read it and this
    sets nothing in code. Without it the cache goes to the fixed in-checkout
    path — except on the CPU backend, which gets none: XLA:CPU entries embed
    the host's CPU features and the tier-1 tests construct hundreds of
    engines that must not write into the checkout."""
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if jax.default_backend() == "cpu":
        return None
    os.makedirs(_DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def force_virtual_cpu(n_devices: int = 1) -> None:
    """Arm an ``n_devices`` virtual CPU platform, even if JAX already
    initialised a different backend. Recipe: set XLA_FLAGS + JAX_PLATFORMS
    (covers subprocesses / not-yet-imported jax), force ``jax_platforms``
    via jax.config, and drop any already-initialised backend so the new
    flags take effect."""
    # XLA_FLAGS is parsed once per process, so for the already-initialised
    # case below we rely on jax_num_cpu_devices (config-time, re-read on
    # client creation) instead.
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.default_backend() == "cpu" and len(jax.devices()) == n_devices:
            return  # already armed (e.g. under tests/conftest.py)
        jax.clear_caches()
        from jax.extend import backend as jeb

        jeb.clear_backends()
    jax.config.update("jax_num_cpu_devices", n_devices)
    jax.config.update("jax_platforms", "cpu")
    got = len(jax.devices("cpu"))
    if got != n_devices:
        raise RuntimeError(
            f"virtual CPU platform has {got} devices, wanted {n_devices}"
        )
