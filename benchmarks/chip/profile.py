"""Reduce a ``jax.profiler`` trace to device busy time, idle gaps and the
time of named device operations.

Only ``jax.profiler.ProfileData`` reads the ``.xplane.pb`` file.  Device
planes are those named ``/device:TPU:<n>``; their operations are the
events of the line ``XLA Ops``, named there by their HLO text; an
operation's stable name is its instruction's, without the ``%`` and the
``.N`` suffix (``%fused_mlp_score.1 = f32[...] custom-call(...)`` is
``fused_mlp_score``).  Busy time is the union of those events' intervals,
averaged over the devices that ran any; idle gaps are the holes in that
union, each named by the host event that overlaps it most.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: host lines that say nothing about what the program was doing
_HOST_NOISE = ("ThreadpoolListener", "$profiler", "ProfilerSession")


def load_events(path) -> dict:
    """{"device": {plane: [(start_ns, end_ns, name)]},
    "host": [(start_ns, end_ns, name)]} of one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.duration_ns > 0
                            and not e.name.startswith(_HOST_NOISE))
    return {"device": device, "host": host}


def op_name(hlo: str) -> str:
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].strip().lstrip("%"))


def union(intervals: Sequence[Tuple[float, float, str]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_events(events: dict, window_s: float) -> dict:
    """Busy seconds, the ten costliest operations by name, and the ten
    longest idle gaps between the first and the last device operation."""
    used = {p: evs for p, evs in events["device"].items() if evs}
    busy_ns = [sum(e - s for s, e in union(evs)) for evs in used.values()]
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    by_name: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for evs in used.values():
        for s, e, hlo in evs:
            name = op_name(hlo)
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
            counts[name] = counts.get(name, 0) + 1
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    for evs in used.values():
        spans = union(evs)
        gaps.extend((a[1], b[0]) for a, b in zip(spans, spans[1:]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"busy_s": busy_s, "window_s": window_s,
            "op_s": by_name, "op_n": counts,
            "breakdown": {
                "device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[_host_doing(events["host"], s, e),
                               (e - s) / 1e9] for s, e in gaps]}}


def _host_doing(host, start: float, end: float) -> str:
    """The host event overlapping [start, end) the most, or "host idle"."""
    best, name = 0.0, "host: nothing traced"
    for s, e, n in host:
        overlap = min(e, end) - max(s, start)
        if overlap > best:
            best, name = overlap, n
    return name


def kernel_seconds(prof: dict, pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for n, s in prof["op_s"].items() if rx.search(n))


def reduce(profile_dir, window_s: float) -> dict:
    """:func:`reduce_events` of the one trace under ``profile_dir``."""
    files = glob.glob(str(Path(profile_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise SystemExit(f"expected one profile under {profile_dir}, "
                         f"found {len(files)}")
    return reduce_events(load_events(files[0]), window_s)
