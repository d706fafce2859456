"""Readers for a model of linear-attention layers beside block-selecting
attention (``models/sala.py``): its three kernels' shares of their rooflines,
%, each the least time the chip could take for the kernel's calls of the decode
segments (the larger of their bytes over the HBM peak and their operations
over the bf16 peak) over the kernel's own device time.

  linear_window_roofline{regex,span}   the state pool's kernel on the linear
      layers. A call on a live row reads the row's state of one layer once and
      writes it once: the segments' ``ssm_state_bytes`` IS that count (the
      program computes it from its own pool's shapes). Operations from the
      published keys: the commit multiplies the pending window's W tokens into
      ``heads x d x d`` values and the read-out multiplies those by the
      window's S slots, ``2 (W + S) heads d d`` a call with W = S = 8.
  block_score_roofline{regex,span}     the block-score kernel. Bytes: the key
      sums it read, the segments' ``index_bytes_read`` (one float32 row of d a
      page a KV head a call). Operations: every row read is multiplied by the
      16 query heads of its KV group, ONE query slot a call taken (the fewest
      a live row has): ``2 d`` a head a row.
  attn_gathered_roofline{regex,span}   the ragged kernel over the chosen
      blocks' page lists. Bytes: the pages FETCHED (``attn_gathered_pages``,
      one (slot, KV head)'s list a program: keys and values of ``page x d``
      bfloat16), not the context's. Operations: a fetched page's tokens are
      scored against and weighed for the 16 heads of the slot's KV group,
      ``4 d`` a head a token.

The queries read, the outputs written and the windows' small tensors are left
out of the bytes, so a share reads low, never high. The trace and the spans
share no clock (``xplane.reduce_device``), so the two sides are rates, as
``mla_roofline.py``'s.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import readers
from peaks import peaks_for
from spec import import_file

_segments = import_file(os.path.join(readers.READER_FILES, "mla_roofline.py"), "chip_reader_")._segments

WINDOW = 8  # slots of a decode window, and of the pending one it commits


def _share(ev, regex: str, span: str, attr: str, cost) -> Optional[float]:
    """``cost(config, the segments' summed attr) -> (bytes, operations)``."""
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    segments = _segments(ev, span, (attr,))
    if kernel_s <= 0 or not segments or "lightning_nh" not in ev.config:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    n_bytes, n_ops = cost(ev.config, sum(v[attr] for _, _, v in segments))
    peaks = peaks_for(ev.device_kind)
    least_s = max(n_bytes / peaks["hbm_bytes_per_s"], n_ops / peaks["bf16_flops_per_s"])
    return 100.0 * (least_s / wall_s) / (kernel_s / ev.device["window_s"])


def _state_cost(config: dict, state_bytes: float) -> tuple[float, float]:
    d = int(config["lightning_head_dim"])
    values = int(config["lightning_nh"]) * d * d
    calls = state_bytes / (2 * 4 * values)  # float32, read + write
    return state_bytes, calls * 2 * (2 * WINDOW) * values


def _score_cost(config: dict, sum_bytes: float) -> tuple[float, float]:
    d = int(config["head_dim"])
    group = int(config["num_attention_heads"]) // int(config["num_key_value_heads"])
    rows = sum_bytes / (4 * d)
    return sum_bytes, rows * group * 2 * d


def _gather_cost(config: dict, pages: float) -> tuple[float, float]:
    d = int(config["head_dim"])
    page = int(config["sparse_config"]["kernel_stride"])
    group = int(config["num_attention_heads"]) // int(config["num_key_value_heads"])
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[config.get("dtype", "bfloat16")]
    return pages * page * d * itemsize * 2, pages * page * group * 4 * d


def linear_window_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    return _share(ev, regex, span, "ssm_state_bytes", _state_cost)


def block_score_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    return _share(ev, regex, span, "index_bytes_read", _score_cost)


def attn_gathered_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    return _share(ev, regex, span, "attn_gathered_pages", _gather_cost)
