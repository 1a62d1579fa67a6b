"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers, with
nothing but ``jax.profiler.ProfileData``.

The arithmetic works on plain lists of ``(name, start_ns, duration_ns)`` so
that it can be checked without a device; ``read`` turns the file into them.
On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Modules``
has one event per executed program (named after the jitted function) and
whose line ``XLA Ops`` has one event per HLO op, nested where an op (a
``while``, a fusion) contains others.
"""

from __future__ import annotations

import re
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read(path) -> dict:
    """``{plane name: {line name: [(event name, start_ns, duration_ns)]}}``."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
            )
    return out


def device_planes(planes: dict) -> dict:
    return {
        n: lines for n, lines in planes.items()
        if n.startswith("/device:") and any(lines.values())
    }


def merged(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> dict[str, float]:
    """Nanoseconds spent in each op name itself: an event's duration less
    the part its nested events cover, so that a ``while`` and its body are
    not counted twice."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, child_ns, dur]

    def close():
        name, _end, child, dur = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, dur - child)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] += min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, 0.0, dur])
    while stack:
        close()
    return out


def program_name(event_name: str) -> str:
    """``jit__decode_group_impl(7f3a…)`` -> ``jit__decode_group_impl``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def reduce(planes: dict, host_window_s: float | None = None) -> dict:
    """Busy time (union of op intervals), time per program and per op, and
    the longest idle gaps, per device and averaged over the devices used."""
    devs = device_planes(planes)
    if not devs:
        return {"devices": 0}
    per_dev, programs, ops = [], {}, {}
    t_min = min(
        s for lines in planes.values() for evs in lines.values()
        for _n, s, _d in evs
    )
    gaps: list[tuple[float, float]] = []
    for i, (_name, lines) in enumerate(sorted(devs.items())):
        op_events = lines.get(OPS_LINE) or [
            e for n, evs in lines.items() if n != MODULES_LINE for e in evs
        ]
        union = merged((s, s + d) for _n, s, d in op_events)
        busy = sum(e - s for s, e in union)
        span = (union[-1][1] - union[0][0]) if union else 0.0
        per_dev.append({"busy_ns": busy, "span_ns": span})
        for name, _s, d in lines.get(MODULES_LINE, ()):
            p = programs.setdefault(program_name(name), {"ns": 0.0, "n": 0})
            p["ns"] += d
            p["n"] += 1
        for name, ns in self_times(op_events).items():
            ops[name] = ops.get(name, 0.0) + ns
        if i == 0:
            gaps = sorted(
                ((b[0], b[0] - a[1]) for a, b in zip(union, union[1:])),
                key=lambda g: -g[1],
            )[:10]
    n = len(per_dev)
    busy_s = sum(d["busy_ns"] for d in per_dev) / n / 1e9
    span_s = max(d["span_ns"] for d in per_dev) / 1e9
    return {
        "devices": n,
        "busy_s": busy_s,
        # The traced window: the host's start-to-stop time, or the span of
        # the device's events where that is longer (never busy > window).
        "window_s": max(span_s, host_window_s or 0.0),
        "span_s": span_s,
        "programs": {
            k: {"s": v["ns"] / n / 1e9, "n": v["n"] / n}
            for k, v in programs.items()
        },
        "ops": sorted(
            ([k, v / n / 1e9] for k, v in ops.items()), key=lambda x: -x[1]
        ),
        # (seconds after the trace's first event, length in seconds)
        "gaps": [[(s - t_min) / 1e9 - g / 1e9, g / 1e9] for s, g in gaps],
        "first_device_event_s": (
            min(s for lines in devs.values() for evs in lines.values()
                for _n, s, _d in evs) - t_min
        ) / 1e9,
    }
