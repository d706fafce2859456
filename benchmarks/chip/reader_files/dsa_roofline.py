"""Readers for the two kernels of a latent cache read through a learned index.

  index_roofline{regex,span}     the index kernel's share of its roofline, %.
  selected_roofline{regex,span}  the selecting attention kernel's, %.

Each is the least time the chip could take for the USEFUL work of that
kernel's calls in the decode segments (the larger of its bytes over the HBM
peak and its operations over the bf16 peak) over that kernel's OWN device
time: nothing an op outside the named call does enters either side, so a
kernel running at the chip's peaks reads 100 and none can read more.

What a call's useful work is, is the mathematics', whatever form computes it:

  index      a query block scores every cached index key of its row once:
             ``index_head_dim`` values a key read (256 B at the published
             width), ``2 * index_n_heads * index_head_dim`` operations a key,
             ONE query a call taken (the segments' ``index_ctx_tokens`` counts
             keys a (live row, forward, layer) call, not queries). The select
             (a search over the scores' bits) is the kernel's own overhead
             and counts as no useful work.
  attention  a query attends the keys the selection left: ``kv_lora_rank +
             qk_rope_head_dim`` values a key (1,152 B), ``2 * (kv_lora_rank
             + qk_rope_head_dim) + 2 * kv_lora_rank`` operations a key a
             head, ONE query a call: the segments' ``attn_sel_tokens``. The
             masked form streams every page of the row (``kv_bytes_read``
             counts that), so at a 6.9k-token context it can read no more
             than 2,048 / 6,900 = 30% of the bandwidth bound: what a
             gathering form would win is this share's distance from 100.

As ``mla_roofline`` (``reader_files/mla_roofline.py``): the trace and the
spans share no clock, so both sides are rates; the kernels' device time
includes their suffix-prefill calls, whose work the segments' attributes do
not count; one query a call is the fewest a live row has. All make a share
read low, never high.
"""

from __future__ import annotations

import re
from typing import Optional

import readers
from peaks import peaks_for
from stats import cluster_by_start

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _index_call_cost(config: dict, keys: float) -> tuple[float, float]:
    """(useful bytes, operations) of index calls that score ``keys`` cached
    keys in all, one query a call."""
    heads, dim = int(config["index_n_heads"]), int(config["index_head_dim"])
    return keys * dim * _ITEMSIZE[config.get("dtype", "bfloat16")], keys * 2 * heads * dim


def _selected_call_cost(config: dict, keys: float) -> tuple[float, float]:
    """(useful bytes, operations) of absorbed attention calls that attend
    ``keys`` selected keys in all, one query a call."""
    rank, rope = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    heads = int(config["num_attention_heads"])
    row_bytes = (rank + rope) * _ITEMSIZE[config.get("dtype", "bfloat16")]
    return keys * row_bytes, keys * heads * (2 * (rank + rope) + 2 * rank)


def _segments(ev, name: str, keys: tuple[str, ...]):
    """One entry a dispatched segment: (start ms, end ms, {key: value}) of the
    spans called ``name`` that carry every key."""
    items = []
    for tr, sp in readers._spans(ev, name):
        values = {k: readers._value(sp, k) for k in keys}
        if any(v is None for v in values.values()):
            continue
        start = readers._abs_start_ms(tr, sp)
        items.append((start, (start, start + float(sp["duration_ms"]), values)))
    return [group[0] for group in cluster_by_start(items, readers.SEGMENT_GAP_MS)]


def _roofline(ev, regex: str, span: str, attr: str, needs: str, cost) -> Optional[float]:
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    segments = _segments(ev, span, (attr,))
    if kernel_s <= 0 or not segments or needs not in ev.config:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    n_bytes, n_ops = cost(ev.config, sum(v[attr] for _, _, v in segments))
    peaks = peaks_for(ev.device_kind)
    least_s = max(n_bytes / peaks["hbm_bytes_per_s"], n_ops / peaks["bf16_flops_per_s"])
    kernel_share = kernel_s / ev.device["window_s"]  # of the device's time, in the slice
    return 100.0 * (least_s / wall_s) / kernel_share


def index_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    return _roofline(ev, regex, span, "index_ctx_tokens", "index_head_dim", _index_call_cost)


def selected_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    return _roofline(ev, regex, span, "attn_sel_tokens", "index_topk", _selected_call_cost)
