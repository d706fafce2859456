"""Shared test fixtures: scriptable fake microservices over LocalTransport
(SURVEY.md §4.4 — fault-injecting in-process services)."""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import sys
from typing import Any

from mcpx.orchestrator.transport import LocalTransport, TransportError


class FakeService:
    """In-process microservice with scriptable failures.

    ``fail_times``: fail the first N calls, then succeed — exercises retry.
    ``always_fail``: every call fails — exercises fallbacks/partial results.
    """

    def __init__(
        self,
        name: str,
        *,
        fail_times: int = 0,
        always_fail: bool = False,
        result: dict[str, Any] | None = None,
        error_status: int = 0,
        retry_after_s: float | None = None,
    ) -> None:
        self.name = name
        self.calls: list[dict[str, Any]] = []
        self._fail_times = fail_times
        self._always_fail = always_fail
        self._result = result
        # Scripted failure shape: an HTTP status (e.g. 404, 429) and an
        # optional Retry-After, for the executor's retryability logic.
        self._error_status = error_status
        self._retry_after_s = retry_after_s

    async def __call__(self, payload: dict[str, Any]) -> dict[str, Any]:
        self.calls.append(payload)
        if self._always_fail or len(self.calls) <= self._fail_times:
            raise TransportError(
                f"{self.name} injected failure #{len(self.calls)}",
                status=self._error_status,
                retry_after_s=self._retry_after_s,
            )
        if self._result is not None:
            return self._result
        return {"service": self.name, "echo": payload}


def make_transport(*services: FakeService, latencies: dict[str, float] | None = None):
    transport = LocalTransport()
    for svc in services:
        transport.register(svc.name, svc, latency_s=(latencies or {}).get(svc.name, 0.0))
    return transport


def release_prefix_cache(eng) -> None:
    """Drop the engine's radix prefix KV cache (engine/prefix_cache.py) so
    allocator-empty assertions see only ROW leaks, not intentionally
    cached prompt-head KV. Quiesced engines only — the worker thread owns
    the tree; these tests poke engine internals between requests exactly
    like the page-leak checks always have. Unpinned nodes are evicted;
    a node still pinned by a leaked row survives and fails the caller's
    ``sequences == 0`` assert, which is the point."""
    eng.config.engine.prefix_cache_entries = 0
    eng._evict_prefixes()
    eng._prefix_cache.check_invariants()


def by_path(name: str, path: str):
    """The module of one ``.py`` file outside the package (a file of
    ``benchmarks/chip``), imported under ``name`` and never copied."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # its dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def one_device():
    """A 1 x 1 mesh over the first virtual CPU device."""
    import jax

    from mcpx.parallel.mesh import make_mesh

    return make_mesh(data=1, model=1, devices=jax.devices()[:1])


@functools.cache
def params_of(cfg, seed: int = 0):
    """``init_params(cfg, PRNGKey(seed))``, drawn once a distinct
    configuration for the process: no test writes to a params tree, and the
    eager draw compiles every leaf's shape again (seconds a call)."""
    import jax

    from mcpx.models.gemma.model import init_params

    return init_params(cfg, jax.random.PRNGKey(seed))


@functools.cache
def compiled():
    """-> (``prefill``, ``decode_chunk_paged``), each under ONE ``jax.jit``
    for the process: the configuration, the mesh and the route are static,
    the params an ARGUMENT (a jit that closes over them folds them in as
    constants and is compiled again by every case), so cases whose shapes
    agree share an executable, and a body that ran op by op is one program.
    Pools and state are arguments too: nothing of a case reaches the next.
    Not for a case that patches what these trace through (the executable of
    another case would answer)."""
    import jax

    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.model import prefill

    route = ("use_pallas", "interpret", "moe_stats", "routing")
    return (
        jax.jit(prefill, static_argnums=(1,), static_argnames=("last_only", *route)),
        jax.jit(decode_chunk_paged, static_argnums=(1,), static_argnames=("mesh", "selection", "commit", *route)),
    )


@contextlib.contextmanager
def count_compiles(substring: str):
    """Count XLA compiles of executables whose ``jax_log_compiles`` message
    mentions ``substring`` — the compile-count acceptance harness shared by
    the hetero/spec segment tests. Yields the live list of matching
    messages; setup/teardown (the private ``jax._src.interpreters.pxla``
    logger, the DEBUG level, the ``jax_log_compiles`` flag) lives HERE so a
    JAX version moving those internals is a one-place fix. Imports are
    local: transport-only test modules import helpers without paying for
    jax."""
    import logging

    import jax

    compiles: list[str] = []

    class _Counter(logging.Handler):
        def emit(self, rec):
            msg = rec.getMessage()
            if substring in msg and "Compiling" in msg:
                compiles.append(msg)

    logger = logging.getLogger("jax._src.interpreters.pxla")
    handler = _Counter()
    old_level = logger.level
    old_flag = jax.config.jax_log_compiles
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    jax.config.update("jax_log_compiles", True)
    try:
        yield compiles
    finally:
        jax.config.update("jax_log_compiles", old_flag)
        logger.removeHandler(handler)
        logger.setLevel(old_level)


def grouped_against_loop(cfg, h, router, experts, layer, live=None, bias=None):
    """``moe_forward`` at a window past the ridge, by the grouped form it
    takes there and by the loop it would take below (the ridge lifted out
    of the way): outputs within the float32 sum's tolerance, the counts a
    held expert and the experts chosen identical, the grouped form's rows and
    steps what the counts alone say (whole tiles), pad slots exactly 0.
    -> (the grouped form's stats, the loop's), for what the case adds."""
    import numpy as np
    import pytest

    from mcpx.models.gemma import moe

    B, S, _ = h.shape
    T, E = B * S, cfg.n_experts_held
    assert T > moe.RIDGE_SLOTS
    out_g, stats_g, chosen_g = moe.moe_forward(h, router, experts, layer, cfg, live, bias)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "RIDGE_SLOTS", T)
        out_l, stats_l, chosen_l = moe.moe_forward(h, router, experts, layer, cfg, live, bias)
    out_g, out_l, stats_g, stats_l = (np.asarray(a) for a in (out_g, out_l, stats_g, stats_l))
    assert np.abs(out_l).max() > 0
    np.testing.assert_allclose(out_g, out_l, rtol=1e-5, atol=1e-5 * np.abs(out_l).max())
    assert stats_g[: E + 1].tolist() == stats_l[: E + 1].tolist()  # counts, touched
    assert (np.asarray(chosen_g) == np.asarray(chosen_l)).all()
    if live is not None:
        assert (out_g[~np.asarray(live)] == 0).all()
    # the loop multiplied every slot by every touched expert; the grouped
    # form whole tiles: what the routing alone says, ceil(count / tile) an
    # expert, one step a tile and none through the kernel
    assert stats_l[E + 1] == T * stats_l[E] and stats_l[E + 2] == stats_l[E]
    tiles = int(np.sum(-(-stats_g[:E] // moe.GROUP_TILE)))
    assert stats_g[E + 1 :].tolist() == [moe.GROUP_TILE * tiles, tiles, 0]
    return stats_g, stats_l
