"""Readers for a latent (MLA) cache read by the absorbed paged kernel.

  mla_roofline{regex,span}        the kernel's share of its roofline, %: the
      least time the chip could take for the kernel calls of the decode
      segments (the larger of their USEFUL bytes over the HBM peak and their
      operations over the bf16 peak) over the kernel's own device time.
  span_attr_share{name,part,rest} sum(part) / (sum(part) + sum(rest)) over the
      spans called ``name``, each counted once a dispatched segment.

What the kernel's calls need, from the shapes (``_latent_call_cost``): a call
reads each context token's cache row ONCE, for the scores and for the values:
``kv_lora_rank + qk_rope_head_dim`` values a token a layer (576 at the
published widths; the 64 lanes of zeros that pad the rotated key's row in the
pool are moved too and are NOT useful bytes), and multiplies it by every
head's query twice: ``2 * (kv_lora_rank + qk_rope_head_dim)`` operations a
head for the score and ``2 * kv_lora_rank`` for the weighted sum. The
segments' ``attn_ctx_tokens`` counts context tokens a (live row, forward,
layer) call; it does not count queries, so ONE query a call is taken, the
fewest a live row has: a lower bound on the operations. The queries read and
the outputs written are left out of the bytes for the same reason. Both make
the share read low, never high.

The trace and the spans share no clock (``xplane.reduce_device``), so the two
sides are rates: the least time the segments' calls need a second of wall,
over the spans' own stretch of wall (first segment's start to last one's
end), against the kernel's share of the profiled slice. The kernel's device
time includes its suffix-prefill calls, whose context the segments'
attributes do not count: low again.
"""

from __future__ import annotations

import re
from typing import Optional

import readers
from peaks import peaks_for
from stats import cluster_by_start


def _latent_call_cost(config: dict, ctx_tokens: float) -> tuple[float, float]:
    """(useful bytes, operations) of absorbed-kernel calls that read
    ``ctx_tokens`` context tokens in all, one query a call, by the
    configuration file's published keys."""
    rank, rope = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    heads = int(config["num_attention_heads"])
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[config.get("dtype", "bfloat16")]
    row_bytes = (rank + rope) * itemsize
    ops_a_head = 2 * (rank + rope) + 2 * rank
    return ctx_tokens * row_bytes, ctx_tokens * heads * ops_a_head


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


def mla_roofline(ev, regex: str, span: str = "engine.segment") -> Optional[float]:
    if not ev.device or not ev.device.get("window_s") or not ev.config or not ev.device_kind:
        return None
    pat = re.compile(regex)
    kernel_s = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    segments = _segments(ev, span, ("attn_ctx_tokens",))
    if kernel_s <= 0 or not segments or "kv_lora_rank" not in ev.config:
        return None
    wall_s = (max(end for _, end, _ in segments) - min(start for start, _, _ in segments)) / 1e3
    if wall_s <= 0:
        return None
    ctx_tokens = sum(v["attn_ctx_tokens"] for _, _, v in segments)
    n_bytes, n_ops = _latent_call_cost(ev.config, ctx_tokens)
    peaks = peaks_for(ev.device_kind)
    least_s = max(n_bytes / peaks["hbm_bytes_per_s"], n_ops / peaks["bf16_flops_per_s"])
    kernel_share = kernel_s / ev.device["window_s"]  # of the device's time, in the slice
    return 100.0 * (least_s / wall_s) / kernel_share


def span_attr_share(ev, name: str, part: str, rest: str) -> Optional[float]:
    segments = _segments(ev, name, (part, rest))
    total = sum(v[part] + v[rest] for _, _, v in segments)
    if not total:
        return None
    return sum(v[part] for _, _, v in segments) / total
