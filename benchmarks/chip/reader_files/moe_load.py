"""How unevenly a window's tokens fell on the experts, from a labelled
counter's deltas around the window (``Evidence.counters_before`` /
``counters_after``).

  counter_load_max_over_mean{endpoint,counter}  over the samples
      ``<counter>{...}`` of ``endpoint`` (one an expert), the largest delta
      over the mean delta: 1.0 is even routing. The mean is over every
      sample the endpoint shows after the window: the program makes one an
      expert it holds when it binds its weights, so an expert no token chose
      counts with a delta of 0. One without a sample before the window
      counts from 0.

None where the endpoint was not fetched, the counter has no sample (a dense
block; a program from before the counter), or no token was routed in the
window: the line then leaves the metric out.
"""

from __future__ import annotations

from typing import Optional


def counter_load_max_over_mean(ev, endpoint: str, counter: str) -> Optional[float]:
    before, after = ev.counters_before.get(endpoint), ev.counters_after.get(endpoint)
    if not isinstance(before, dict) or not isinstance(after, dict):
        return None
    deltas = [
        float(v) - float(before.get(key, 0.0))
        for key, v in after.items()
        if key.startswith(counter + "{") and isinstance(v, (int, float))
    ]
    total = sum(deltas)
    if not deltas or total <= 0:
        return None
    return max(deltas) / (total / len(deltas))
