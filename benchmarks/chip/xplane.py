"""Reduction of a JAX profiler trace to the benchmark's device metrics.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``tests/recorded_trace.json``) without a chip:

  1. ``read_xplane(dir)``: the ``.xplane.pb`` files under a profile directory
     -> per device plane, the op events ``[name, start_ns, duration_ns]`` of
     its "XLA Ops" line. Needs ``jax`` (``jax.profiler.ProfileData``), so
     the harness runs it as its own short process with JAX held to the CPU
     backend: ``python xplane.py <profile-dir> <out.json>``.
  2. ``reduce_device(planes, wall_s)``: pure. Busy time is the union of the
     op intervals on each device, averaged over the devices used; the
     window is the profiled slice: the longer of ``wall_s`` (the host's
     wall from ``/profile/start`` answered to ``/profile/stop`` sent, which
     the profiler covers) and the span from the first op's start to the
     last op's end, so idle time at the slice's edges counts as idle; op
     self-time totals by name (ops nest: a ``while`` holds its body);
     the longest idle gaps, each named by the op
     that ended before it (the program writes no host annotations into the
     profiler's trace yet, so what the host was doing is not attributable).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

from stats import union_length

OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def read_xplane(profile_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    )
    planes: dict[str, list] = {}
    seen_planes: list[str] = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            seen_planes.append(plane.name)
            if not DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                events = planes.setdefault(plane.name, [])
                for ev in line.events:
                    events.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return {"files": [os.path.basename(p) for p in paths], "plane_names": seen_planes,
            "planes": planes}


def op_label(name: str) -> str:
    """A stable short label for an op event: HLO text such as
    ``%copy.176 = bf16[16,16,257,16,128]{...} copy(...)`` keeps its name,
    result shape and opcode (a tuple result reads ``(tuple)``); a bare name
    is kept as it is."""
    m = re.match(r"^%?([\w.\-]+) = ", name)
    if not m:
        return name.lstrip("%")[:120]
    rest = name[m.end():]
    if rest.startswith("("):  # tuple result type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = "(tuple)", rest[i + 1:].lstrip()
    else:
        sm = re.match(r"(\w+\[[\d,]*\])\S*\s*", rest)
        if not sm:
            return m.group(1)
        shape, rest = sm.group(1), rest[sm.end():]
    om = re.match(r"([\w\-]+)\(", rest)
    return f"{m.group(1)} {shape} {om.group(1)}" if om else f"{m.group(1)} {shape}"


def reduce_device(planes: dict[str, list], wall_s: float = 0.0, top: int = 10) -> dict:
    """``planes``: device plane name -> ``[name, start_ns, duration_ns]``
    events; ``wall_s``: the profiled slice's wall on the host's clock.
    Returns busy_s (averaged over devices), window_s, per-op seconds
    (averaged over devices), the top ops and the longest idle gaps."""
    used = {p: evs for p, evs in planes.items() if evs}
    if not used:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "ops": {}, "device_ops": [],
                "idle_gaps": []}
    t_lo = min(e[1] for evs in used.values() for e in evs)
    t_hi = max(e[1] + e[2] for evs in used.values() for e in evs)
    n = len(used)
    busy_ns = 0.0
    ops: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    for evs in used.values():
        busy_ns += union_length((e[1], e[1] + e[2]) for e in evs)
        ordered = sorted(evs, key=lambda e: (e[1], -e[2]))
        # Ops nest on the line (a ``while`` holds its body's ops): an op's
        # seconds are its SELF time, its span minus its direct children's,
        # so a loop is not counted once for itself and again for its body.
        self_ns = [e[2] for e in ordered]
        stack: list[int] = []
        for i, (_name, start, dur) in enumerate(ordered):
            while stack and ordered[stack[-1]][1] + ordered[stack[-1]][2] <= start:
                stack.pop()
            if stack:
                self_ns[stack[-1]] -= dur
            stack.append(i)
        for (name, _start, _dur), own in zip(ordered, self_ns):
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + max(0.0, own) / n
        end, last = t_lo, "window-start"
        for name, start, dur in ordered:
            if start > end:
                gaps.append((start - end, f"after {op_label(last)}"))
            if start + dur > end:
                end, last = start + dur, name
        if t_hi > end:
            gaps.append((t_hi - end, f"after {op_label(last)}"))
    # The host's clock and the trace's share no origin, so idle time at the
    # slice's edges cannot be split into before and after: one gap.
    edge_ns = wall_s * 1e9 - (t_hi - t_lo)
    if edge_ns > 0:
        gaps.append((edge_ns, "slice edges: before the first op or after the last"))
    gaps.sort(reverse=True)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "busy_s": busy_ns / n / 1e9,
        "window_s": max(t_hi - t_lo, wall_s * 1e9) / 1e9,
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "device_ops": [[k, v / 1e9] for k, v in top_ops],
        "idle_gaps": [[label, g / 1e9] for g, label in gaps[:top]],
    }


def main(argv: list[str]) -> int:
    profile_dir, out_path = argv[1], argv[2]
    raw = read_xplane(profile_dir)
    with open(out_path, "w") as f:
        json.dump(raw, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
