"""Reader for the routed experts' kernel (``routed_experts``): its share of
its roofline.

  routed_experts_roofline{regex,span}   %: the least time the chip could take
      to fetch the experts the decode segments' forwards touched (each
      touched expert's matrices read ONCE a (forward, layer): what the kernel
      does) over the kernel's own device time.

What a call needs (``_expert_bytes``), from the published keys: a touched
expert's matrices, ``3 x hidden x moe_intermediate`` values for a gated
expert on the model's own width, ``2 x moe_latent_size x moe_intermediate``
for an expert of two matrices that works in a latent. The segments'
``moe_experts_touched`` counts the (forward, layer, expert) triples with at
least one live token. The bytes bind: a step multiplies a window of <= 64
slots by 11-13 MB of weights, far under the ridge (~240 rows). The rows read
and the output written (a few hundred KB a call) are left out, so the share
reads low, never high. The kernel's device time includes its calls in
suffix prefills, whose touched experts the segments' attributes do not
count: low again.

Rates on both sides, as ``mla_roofline.py``: the trace and the spans share no
clock.
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

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _expert_bytes(config: dict) -> Optional[int]:
    """Bytes of one routed expert's matrices, by the configuration file's
    published keys; None where the file has no routed expert."""
    width = config.get("moe_intermediate_size")
    if not width:
        return None
    itemsize = _ITEMSIZE[config.get("dtype", "bfloat16")]
    if config.get("moe_latent_size"):
        return 2 * int(config["moe_latent_size"]) * int(width) * itemsize
    return 3 * int(config["hidden_size"]) * int(width) * itemsize


def routed_experts_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    segments = _segments(ev, span, ("moe_experts_touched",))
    expert_bytes = _expert_bytes(ev.config)
    if kernel_s <= 0 or not segments or not expert_bytes:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    n_bytes = expert_bytes * sum(v["moe_experts_touched"] for _, _, v in segments)
    least_s = n_bytes / peaks_for(ev.device_kind)["hbm_bytes_per_s"]
    kernel_share = kernel_s / ev.device["window_s"]  # of the device's time, in the slice
    return 100.0 * (least_s / wall_s) / kernel_share
