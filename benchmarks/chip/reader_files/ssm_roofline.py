"""Readers for a model with recurrent (state-space) layers, whose decode
windows run through the state pool's kernel (``ssm_window``).

  ssm_state_roofline{regex,span}   the kernel's share of its roofline, %: the
      least time the chip could take for the kernel calls of the decode
      segments over the kernel's own device time.
  span_attr_share_of{name,part,rest}  sum(part) / (sum(part) + sum of every
      attribute in ``rest``) over the spans called ``name``, each counted
      once a dispatched segment.

What the kernel's calls need (``_state_call_cost``): a call on a live row
reads the row's state of one layer once and writes it once. The segments'
``ssm_state_bytes`` IS that count (live rows x forwards x recurrent layers x
a slot's bytes x 2: the program computes it from its own pool's shapes), so
the bytes are read off the spans; the operations are from the published
keys: the commit multiplies the pending window's ``W`` tokens into ``heads x
head_dim x state`` values and the read-out multiplies those by the window's
``S`` slots, ``2 x (W + S) x heads x head_dim x state`` a call with ``W = S =
8``, the decode window's width: 33.5 MFLOP against 8.4 MB, 0.17 us at the
bf16 peak against 10.2 us at the HBM's: the bytes bind sixty times over. The window's small tensors (a few hundred KB a call) are left
out of the bytes, so the share reads low, never high.

The trace and the spans share no clock (``xplane.reduce_device``), so the two
sides are rates, as ``mla_roofline.py``'s: the least time the segments' calls
need a second of wall, over the spans' own stretch of wall, against the
kernel's share of the profiled slice.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import readers
from peaks import peaks_for
from spec import import_file

# One entry a dispatched segment, (start ms, end ms, {key: value}): the latent
# kernel's reader file has it.
_segments = import_file(os.path.join(readers.READER_FILES, "mla_roofline.py"), "chip_reader_")._segments

WINDOW = 8  # slots of a decode window, and of the pending one it commits


def _state_call_cost(config: dict, state_bytes: float) -> tuple[float, float]:
    """(bytes, operations) of state-kernel calls that moved ``state_bytes``
    (each call one slot's state of a layer, read once and written once)."""
    values = int(config["mamba_num_heads"]) * int(config["mamba_head_dim"]) * int(config["ssm_state_size"])
    calls = state_bytes / (2 * 4 * values)  # float32, read + write
    return state_bytes, calls * 2 * (2 * WINDOW) * values


def ssm_state_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    segments = _segments(ev, span, ("ssm_state_bytes",))
    if kernel_s <= 0 or not segments or "ssm_state_size" not in ev.config:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    n_bytes, n_ops = _state_call_cost(ev.config, sum(v["ssm_state_bytes"] for _, _, v in segments))
    peaks = peaks_for(ev.device_kind)
    least_s = max(n_bytes / peaks["hbm_bytes_per_s"], n_ops / peaks["bf16_flops_per_s"])
    kernel_share = kernel_s / ev.device["window_s"]  # of the device's time, in the slice
    return 100.0 * (least_s / wall_s) / kernel_share


def span_attr_share_of(ev, name: str, part: str, rest: list) -> Optional[float]:
    segments = _segments(ev, name, (part, *rest))
    total = sum(sum(v.values()) for _, _, v in segments)
    if not total:
        return None
    return sum(v[part] for _, _, v in segments) / total
