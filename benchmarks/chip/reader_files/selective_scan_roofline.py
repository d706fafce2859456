"""Reader for a model whose recurrent layers are Mamba-1 SELECTIVE SCANS, walked
by one kernel body under two call names (``mcpx/engine/kernels/
selective_scan.py``).

  selective_scan_roofline{regex,span}   a call form's share of its roofline, %:
      the least time the chip could take for that form's calls over the
      form's own device time. ``regex`` names the form in the device trace;
      ``span`` says where its calls are counted: ``engine.segment`` the decode
      windows', ``engine.prefill`` the admission prefills'.

**The share is read against the BYTES.** The kernel's bound is not the HBM: a
token of a channel costs 16 exponentials on a unit with no published peak and
half a dozen vector operations beside each, nothing for the multiplier. What a
call MUST move is known from its shapes, so that is what the least time is
taken from (``max(bytes / HBM peak, operations / bf16 peak)`` as
``ssm_roofline.py``; the operations, ``6 x N x I`` a token walked, never bind).
The share is therefore EXPECTED to read well under 100% on the prefill form and
nearer its floor on the window form; it may read low, never high.

What a call moves (``_call_bytes``; I = mamba_expand x hidden_size channels, N
= mamba_d_state, float32 throughout):

  window   a call on a live row reads the row's state of one layer once and
           writes it once: the segments' ``ssm_state_bytes`` IS that count
           (live rows x forwards x J layers x N x I x 4 x 2, the program's own),
           so the calls are read off it. Beside the state a call reads ``A`` [N,
           I], ``dt`` and ``x`` of the pending window's 8 tokens and this
           window's 8 (2 x 16 x I), ``B`` of those 16 and ``C`` of this window's
           8 (24 x N), and writes ``y`` (8 x I).
  prefill  the prefills' ``scan_slots`` counts the slots of each cohort's ``A x
           T`` window times the J layers, all of them streamed whatever a row's
           length: ``dt``, ``x`` in and ``y`` out (3 x I) and ``B``, ``C`` (2 x
           N) a slot; ``ssm_state_bytes`` there is the states the calls wrote
           (rows x layers x N x I x 4), and each call reads ``A`` once, which is
           as much again.

Nothing else is counted: not the lanes that pad ``B`` and ``C`` to a lane
width, not a re-read. The trace and the spans share no clock
(``xplane.reduce_device``), so the two sides are rates, as ``ssm_roofline.py``'s.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import readers
from peaks import peaks_for
from spec import import_file

# One entry a dispatched segment or cohort, (start ms, end ms, {key: value}):
# the latent kernel's reader file has it.
_segments = import_file(os.path.join(readers.READER_FILES, "mla_roofline.py"), "chip_reader_")._segments

WINDOW = 8  # slots of a decode window, and of the pending one it commits
STEP_OPS = 6  # vector operations a (state, channel) pair a token, the exponential apart


def _widths(config: dict) -> tuple[int, int]:
    return int(config["mamba_expand"]) * int(config["hidden_size"]), int(config["mamba_d_state"])


def _call_bytes(config: dict, span: str, attrs: dict) -> tuple[float, float]:
    """(bytes, operations) of the calls one span's attributes count."""
    I, N = _widths(config)
    state = 4 * N * I  # one slot's state of one layer, and ``A``
    if span == "engine.prefill":
        slots, calls = attrs["scan_slots"], attrs["ssm_state_bytes"] / state
        return slots * 4 * (3 * I + 2 * N) + calls * 2 * state, slots * STEP_OPS * N * I
    calls = attrs["ssm_state_bytes"] / (2 * state)
    moved = 4 * (2 * 2 * WINDOW * I + WINDOW * I + 3 * WINDOW * N)
    return calls * (3 * state + moved), calls * 2 * WINDOW * STEP_OPS * N * I


def selective_scan_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    keys = ("scan_slots", "ssm_state_bytes") if span == "engine.prefill" else ("ssm_state_bytes",)
    segments = _segments(ev, span, keys)
    if kernel_s <= 0 or not segments or "mamba_d_state" not in ev.config:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    totals = {k: sum(v[k] for _, _, v in segments) for k in keys}
    n_bytes, n_ops = _call_bytes(ev.config, span, totals)
    peaks = peaks_for(ev.device_kind)
    least_s = max(n_bytes / peaks["hbm_bytes_per_s"], n_ops / peaks["bf16_flops_per_s"])
    kernel_share = kernel_s / ev.device["window_s"]  # of the device's time, in the slice
    return 100.0 * (least_s / wall_s) / kernel_share
