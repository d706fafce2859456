"""Device-trace capture behind ``POST /profile/start|stop``: one profiler
session, flushed as one ``.xplane.pb``.

Why not ``jax.profiler.start_trace`` / ``stop_trace``: ``stop_trace`` ends in
``ProfilerSession.stop_and_export``, which writes the capture twice — the
``.xplane.pb`` that XProf, TensorBoard and ``jax.profiler.ProfileData`` read,
and a gzipped Chrome-trace JSON of the same events with every event's full
HLO text. The JSON costs four to five times the collection itself: on a v5e
an 8 s capture of live serving (1.2 M device events) took 28 s to collect
and more than 120 s through ``stop_trace``, past the server's own request
timeout (PERF.md, PR 25). The session's ``stop()`` returns the collected
``XSpace`` as bytes; writing them where ``stop_and_export`` would have is the
whole export. The Python tracer is off: it hooks every call on every thread
of a live server, and the worker's phases reach the capture as
``TraceAnnotation``s through the host tracer (docs/observability.md).

``jax._src.lib._profiler`` is where ``jax.profiler.ProfileOptions`` lives;
jax exposes the options publicly and the session only through
``start_trace``. One process holds one session at a time (jax's own
``start_trace`` included); the caller serialises start and stop.
"""

from __future__ import annotations

import os
import socket
import time
from typing import Any


def start() -> Any:
    """Begin capturing; returns the session to hand to ``stop``."""
    import jax
    from jax._src.lib import _profiler

    # The TPU tracer attaches to an initialised backend; created before it,
    # the session would record no device plane (jax's start_trace does the
    # same).
    jax.devices()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return _profiler.ProfilerSession(opts)


def stop(session: Any, log_dir: str) -> str:
    """End the capture and write it as
    ``<log_dir>/plugins/profile/<time>/<host>.xplane.pb`` (the layout
    TensorBoard's profile plugin and XProf open); returns the path."""
    xspace = session.stop()
    run_dir = os.path.join(
        log_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S")
    )
    os.makedirs(run_dir, exist_ok=True)
    path = os.path.join(run_dir, f"{socket.gethostname()}.xplane.pb")
    with open(path, "wb") as f:
        f.write(xspace)
    return path
