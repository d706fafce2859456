"""Readers of a value as it stands after the window, from an endpoint the
harness fetched (``Evidence.counters_after``): a gauge, not a counter's delta.

  endpoint_value{endpoint,path,scale}         one number at ``path``: dotted into
                                              a JSON body, or one whole sample of
                                              ``/metrics``, labels and all
  endpoint_spread{endpoint,path,field,scale}  largest minus smallest over the
                                              list at ``path``: of its numbers,
                                              or of ``field`` of its objects

Each returns None where the endpoint was not fetched, the path leads nowhere
or a value is not a number (a program that does not serve the sample yet, a
backend without allocator statistics): the line then leaves the metric out.
"""

from __future__ import annotations

from typing import Optional


def _at(body, path: str):
    """What lies at ``path`` in an endpoint's body, or None."""
    if isinstance(body, dict) and path in body:  # one sample of /metrics, whole
        return body[path]
    for part in filter(None, path.split(".")):
        if not isinstance(body, dict) or part not in body:
            return None
        body = body[part]
    return body


def _number(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) and not isinstance(v, bool) else None


def endpoint_value(ev, endpoint: str, path: str, scale: float = 1.0) -> Optional[float]:
    v = _number(_at(ev.counters_after.get(endpoint), path))
    return None if v is None else scale * v


def endpoint_spread(ev, endpoint: str, path: str, field: str = "", scale: float = 1.0) -> Optional[float]:
    items = _at(ev.counters_after.get(endpoint), path)
    if not isinstance(items, list) or not items:
        return None
    values = [_number(i.get(field) if field and isinstance(i, dict) else i) for i in items]
    if any(v is None for v in values):
        return None
    return scale * (max(values) - min(values))
