"""Reduction of a JAX profiler trace to the benchmark's device numbers.

:func:`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event lists; :func:`reduce_events` turns those into:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- ``module_s`` / ``module_calls``: device time and executions of each
  jitted program, by name;
- ``device_ops``: the device operations that took most time, each named
  by its HLO instruction and result shape;
- ``idle_gaps``: idle time between device operations, by what the host
  was doing at the middle of each gap (the innermost host event then).

Events are ``(plane, line, name, start_ns, dur_ns)`` tuples, so a small
recorded trace can be kept as JSON and reduced again in a test.
"""
from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NAMED_GAPS = 4096  # the longest gaps, each named by its host event
HOST_LOOKBACK = 4096  # host events searched back from a gap's middle


def read_xplane(path: str) -> list[tuple]:
    from jax.profiler import ProfileData

    out = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            float(e.start_ns), float(e.duration_ns)))
    return out


def union_s(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total length (s) of the union of ``[t0, t1)`` ns intervals, and the
    merged intervals in order."""
    merged: list[list[float]] = []
    for t0, t1 in sorted(intervals):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return sum(b - a for a, b in merged) / 1e9, merged


def _host_at(host: list, starts: list, t: float) -> str:
    """Name of the innermost host event (latest start) running at ``t``."""
    i = bisect.bisect_right(starts, t)
    for j in range(i - 1, max(-1, i - 1 - HOST_LOOKBACK), -1):
        if host[j][1] > t:
            return host[j][2]
    return "(no host event)"


def _top(d: dict, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


def reduce_events(events: list[tuple], window_s: float) -> dict | None:
    """Device numbers of one traced window, or None when no operation ran
    on a device."""
    ops: dict[str, list] = {}
    module_s: dict[str, float] = {}
    module_calls: dict[str, int] = {}
    op_s: dict[str, float] = {}
    host = []
    for plane, line, name, t0, dur in events:
        if plane == HOST_PLANE:
            host.append((t0, t0 + dur, name))
        elif line == OPS_LINE:
            ops.setdefault(plane, []).append((t0, t0 + dur))
            name = name.split("{")[0]  # "%copy.5 = u32[16,65536,250]"
            op_s[name] = op_s.get(name, 0.0) + dur / 1e9
        elif line == MODULES_LINE:
            base = re.sub(r"\(\d+\)$", "", name)
            module_s[base] = module_s.get(base, 0.0) + dur / 1e9
            module_calls[base] = module_calls.get(base, 0) + 1
    if not ops:
        return None
    busy = []
    gaps: dict[str, float] = {}
    host.sort()
    starts = [h[0] for h in host]
    for plane, iv in sorted(ops.items()):
        b, merged = union_s(iv)
        busy.append(b)
        if plane != min(ops):
            continue  # gaps are named on the first device
        spans = sorted(((g1 - g0, g0, g1) for (_, g0), (g1, _)
                        in zip(merged, merged[1:])), reverse=True)
        for rank, (length, g0, g1) in enumerate(spans):
            name = "(shorter gaps, not named)"
            if rank < NAMED_GAPS:
                name = _host_at(host, starts, (g0 + g1) / 2)
            gaps[name] = gaps.get(name, 0.0) + length / 1e9
    return dict(
        busy_s=sum(busy) / len(busy),
        window_s=window_s,
        devices=len(ops),
        module_s=module_s,
        module_calls=module_calls,
        device_ops=_top(op_s),
        idle_gaps=_top(gaps),
    )


def module_time(dev: dict, program: str) -> tuple[float, int]:
    """Device seconds and executions of the jitted ``program`` (matched
    as ``jit_<program>`` or ``<program>``)."""
    s = c = 0
    for name, t in dev["module_s"].items():
        if name in (program, f"jit_{program}"):
            s += t
            c += dev["module_calls"][name]
    return s, c
