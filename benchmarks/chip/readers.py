"""The per-layer metric readers: a small vocabulary that files extend.

A metric file (``metrics/<metric>.json``) names one reader and its
arguments. Every reader takes the run's collected evidence (``Evidence``)
and returns a number, or None when it finds nothing to read — the harness
then leaves that metric out of the line. A reader is looked up among the
nine below first, then among the public functions that the files of
``reader_files/*.py`` define, each ``(ev, **args)`` (``vocabulary``); a new
reader is a new file there, which may import this module and ``stats``.

  client_quantile{q}                 quantile of the load generator's own
                                     lateness samples (closed: reply-to-next-
                                     send gap; paced: send lateness), ms
  span_self_quantile{name,q}         quantile of the self time (ms) of spans
                                     called ``name`` ("@root": the root span)
  span_attr_ratio{name,num,den,      sum(num) / sum(den) over the spans called
      num_per,den_per}               ``name``; an attribute, or "@duration_ms";
                                     ``*_per`` "span" (every span) or
                                     "segment" (once per dispatched segment:
                                     the per-row spans of one segment share
                                     its start, forwards and wall)
  span_cluster_size{name}            mean number of spans called ``name`` per
                                     dispatched segment (rows per segment)
  trace_attr_mean{terms}             per retained trace, the signed sum of
                                     span attributes ``[[span, attr, sign]]``;
                                     mean over the traces that have the first
  counter_delta_ratio{endpoint,      (after - before) of counters read from
      num,den,scale}                 ``endpoint`` around the window: sum of
                                     ``num`` paths over sum of ``den``. The
                                     harness fetches every ``endpoint`` that
                                     a metric of the cell names in its args;
                                     a path is dotted into a JSON body, or one
                                     whole sample of ``/metrics``, labels and
                                     all (``run.prom_samples``)
  device_op_share{regex,scale}       device seconds of the ops whose label
                                     matches ``regex`` over the traced window
  device_idle_share{scale}           1 - busy / window of the device trace
  memory_in_use{scale}               bytes in use after the window on the
                                     fullest device
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import re
from typing import Callable, Optional

from spec import import_file
from stats import cluster_by_start, quantile, span_self_ms

SEGMENT_GAP_MS = 20.0  # per-trace clocks agree to ~1 ms; segments last >> 20 ms
READER_FILES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "reader_files")


@dataclasses.dataclass
class Evidence:
    """What one run collected for the readers."""

    gen_late_ms: list  # the generator's lateness samples inside the window
    traces: list  # GET /traces/{id} bodies of the window's plans
    counters_before: dict  # endpoint -> JSON body at window start
    counters_after: dict  # endpoint -> JSON body after the window
    device: Optional[dict]  # xplane.reduce_device(...) of the traced slice
    memory_in_use_bytes: Optional[int]  # after the window, fullest device
    # For a reader that computes a kernel's operations and bytes: the
    # configuration file as run, and the chip's kind (``peaks.peaks_for``).
    config: Optional[dict] = None
    device_kind: Optional[str] = None


def _spans(ev: Evidence, name: str):
    """(trace, span) for every span called ``name``; "@root" = parentless."""
    for tr in ev.traces:
        for sp in tr.get("tree", []):
            if (name == "@root" and sp.get("parent_id") is None) or sp.get("name") == name:
                yield tr, sp


def _abs_start_ms(tr: dict, sp: dict) -> float:
    return float(tr["started_at"]) * 1e3 + float(sp["start_ms"])


def _value(sp: dict, key: str) -> Optional[float]:
    if key == "@duration_ms":
        return float(sp["duration_ms"])
    v = (sp.get("attrs") or {}).get(key)
    return None if v is None else float(v)


def _summed(ev: Evidence, name: str, key: str, per: str) -> Optional[float]:
    pairs = [
        (_abs_start_ms(tr, sp), v)
        for tr, sp in _spans(ev, name)
        if (v := _value(sp, key)) is not None
    ]
    if not pairs:
        return None
    if per == "span":
        return sum(v for _, v in pairs)
    if per == "segment":
        return sum(group[0] for group in cluster_by_start(pairs, SEGMENT_GAP_MS))
    raise ValueError(f"'per' must be span or segment, got {per!r}")


def client_quantile(ev: Evidence, q: float) -> Optional[float]:
    return quantile(ev.gen_late_ms, q)


def span_self_quantile(ev: Evidence, name: str, q: float) -> Optional[float]:
    return quantile(
        (span_self_ms(sp, tr["tree"]) for tr, sp in _spans(ev, name)), q
    )


def span_attr_ratio(
    ev: Evidence, name: str, num: str, den: str, num_per: str = "span", den_per: str = "span"
) -> Optional[float]:
    n = _summed(ev, name, num, num_per)
    d = _summed(ev, name, den, den_per)
    if n is None or not d:
        return None
    return n / d


def span_cluster_size(ev: Evidence, name: str) -> Optional[float]:
    items = [(_abs_start_ms(tr, sp), 1) for tr, sp in _spans(ev, name)]
    groups = cluster_by_start(items, SEGMENT_GAP_MS)
    if not groups:
        return None
    return len(items) / len(groups)


def trace_attr_mean(ev: Evidence, terms: list) -> Optional[float]:
    totals = []
    for tr in ev.traces:
        by_name: dict[str, list] = {}
        for sp in tr.get("tree", []):
            by_name.setdefault(sp.get("name"), []).append(sp)
        if not by_name.get(terms[0][0]):
            continue
        total = 0.0
        for span_name, attr, sign in terms:
            for sp in by_name.get(span_name, []):
                v = _value(sp, attr)
                if v is not None:
                    total += float(sign) * v
        totals.append(total)
    if not totals:
        return None
    return sum(totals) / len(totals)


def histogram(ev: Evidence, name: str, attr: str) -> dict:
    """Count of spans called ``name`` by the whole-number value of ``attr``
    (for the run's info line: the mix of plan lengths the window served)."""
    counts: dict[int, int] = {}
    for _tr, sp in _spans(ev, name):
        v = _value(sp, attr)
        if v is not None:
            counts[int(v)] = counts.get(int(v), 0) + 1
    return dict(sorted(counts.items()))


def _path(obj, dotted: str) -> Optional[float]:
    if isinstance(obj, dict) and dotted in obj:  # one sample of /metrics, whole
        obj, dotted = obj[dotted], ""
    for part in filter(None, dotted.split(".")):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return float(obj) if isinstance(obj, (int, float)) else None


def counter_delta_ratio(
    ev: Evidence, endpoint: str, num: list, den: list, scale: float = 1.0
) -> Optional[float]:
    before, after = ev.counters_before.get(endpoint), ev.counters_after.get(endpoint)
    if before is None or after is None:
        return None

    def delta(paths: list) -> Optional[float]:
        total = 0.0
        for p in paths:
            a, b = _path(after, p), _path(before, p)
            if a is None or b is None:
                return None
            total += a - b
        return total

    n, d = delta(num), delta(den)
    if n is None or not d:
        return None
    return scale * n / d


def device_op_share(ev: Evidence, regex: str, scale: float = 100.0) -> Optional[float]:
    if not ev.device or not ev.device.get("window_s"):
        return None
    pat = re.compile(regex)
    secs = sum(s for label, s in ev.device["ops"].items() if pat.search(label))
    return scale * secs / ev.device["window_s"]


def device_idle_share(ev: Evidence, scale: float = 100.0) -> Optional[float]:
    if not ev.device or not ev.device.get("window_s"):
        return None
    return scale * (1.0 - ev.device["busy_s"] / ev.device["window_s"])


def memory_in_use(ev: Evidence, scale: float = 1e-9) -> Optional[float]:
    if ev.memory_in_use_bytes is None:
        return None
    return scale * ev.memory_in_use_bytes


READERS: dict[str, Callable[..., Optional[float]]] = {
    f.__name__: f
    for f in (
        client_quantile, span_self_quantile, span_attr_ratio, span_cluster_size,
        trace_attr_mean, counter_delta_ratio, device_op_share, device_idle_share,
        memory_in_use,
    )
}


def vocabulary(reader_dir: str = READER_FILES) -> dict[str, Callable[..., Optional[float]]]:
    """Every reader by name: ``READERS`` and the public functions defined in
    the files of ``reader_dir``. A name defined twice is an error."""
    found = dict(READERS)
    where = {name: "readers.py" for name in READERS}
    files = sorted(f for f in os.listdir(reader_dir) if f.endswith(".py")) if os.path.isdir(reader_dir) else []
    for fname in files:
        module = import_file(os.path.join(reader_dir, fname), "chip_reader_")
        for name, fn in inspect.getmembers(module, inspect.isfunction):
            if name.startswith("_") or fn.__module__ != module.__name__:
                continue  # a helper, or a function the file only imported
            if name in found:
                raise ValueError(f"reader {name!r} is defined in {where[name]} and in {fname}")
            found[name], where[name] = fn, fname
    return found


def reader_named(reader: str, found: dict) -> Callable[..., Optional[float]]:
    if reader not in found:
        raise KeyError(
            f"unknown reader {reader!r} (vocabulary: {sorted(found)}; looked in readers.py "
            "and reader_files/*.py)"
        )
    return found[reader]


def read_metric(ev: Evidence, reader: str, args: dict, found: Optional[dict] = None) -> Optional[float]:
    """``found`` is a ``vocabulary(...)`` made once; None looks the default up."""
    return reader_named(reader, vocabulary() if found is None else found)(ev, **args)
