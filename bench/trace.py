"""Reduce a profiler trace to device busy time, idle gaps and op times.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/`` (read with ``jax.profiler.ProfileData``
after the server has exited; reading a file touches no device).  Each
device is a plane named ``/device:<KIND>:<i>`` (not ``CPU``); its line
``XLA Ops`` holds one event per executed operation and ``XLA Modules``
one per executed program.  The host planes' events name what the host
was doing in each idle gap.

Output (``reduce_trace``):

* ``window_s``: first to last event on the devices (the capture's own
  start and stop, on the host, are left out);
* ``busy_s``: per device, the union of its program executions (``XLA
  Modules``; the op events leave the loop control of a program
  uncovered), averaged over devices;
* ``modules``: per program name, ``[executions, device seconds]``;
* ``device_ops``: the 10 innermost ops with most device time (seconds,
  averaged over devices; ops that enclose others, such as a loop, are
  left out so that no time counts twice);
* ``idle_gaps``: the 10 longest gaps between busy intervals on the
  first device, each named by the innermost host event spanning it.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(profile_dir: str) -> str | None:
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_planes(path: str) -> list:
    """``[(plane name, {line name: [(name, start_ns, end_ns), ...]})]``."""
    from jax.profiler import ProfileData
    if path.endswith(".pbtxt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.end_ns))
                for ev in line.events)
        out.append((plane.name, lines))
    return out


def is_device(plane_name: str) -> bool:
    return (plane_name.startswith("/device:")
            and not plane_name.startswith("/device:CPU"))


def merge(intervals: list) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def innermost(events: list) -> list:
    """The events that enclose no other event of their line."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, (name, s, e) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e:
            out.append((name, s, e))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = u32[...] fusion(...)`` -> ``%fusion.12``."""
    return hlo.split(" = ", 1)[0]


def reduce_trace(planes: list) -> dict | None:
    """See the module docstring; None when no device op was captured."""
    devices = [ls for n, ls in planes if is_device(n)
               and (ls.get(OPS_LINE) or ls.get(MODULES_LINE))]
    if not devices:
        return None
    every = [(s, e) for ls in devices for evs in ls.values()
             for _, s, e in evs]
    t0 = min(s for s, _ in every)
    t1 = max(e for _, e in every)
    busy = [merge([(s, e) for _, s, e in
                   (ls.get(MODULES_LINE) or ls.get(OPS_LINE, []))])
            for ls in devices]
    op_ns: dict = {}
    modules: dict = {}
    for ls in devices:
        for name, s, e in innermost(ls.get(OPS_LINE, [])):
            op_ns[op_name(name)] = op_ns.get(op_name(name), 0.0) + (e - s)
        for name, s, e in ls.get(MODULES_LINE, []):
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += (e - s) / 1e9
    n_dev = len(devices)
    host = [ev for n, ls in planes if not is_device(n)
            for evs in ls.values() for ev in evs if ev[2] > ev[1]]
    gaps = []
    edges = [[t0, t0]] + busy[0] + [[t1, t1]]
    for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(sum(e - s for s, e in b) for b in busy) / n_dev / 1e9,
        "devices": n_dev,
        "modules": modules,
        "device_ops": [[name, ns / n_dev / 1e9] for name, ns in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_host_activity(host, a, b), ln / 1e9]
                      for ln, a, b in gaps[:10]],
    }


def _host_activity(host: list, a: float, b: float) -> str:
    """The innermost host event spanning the middle of ``[a, b]``."""
    mid = (a + b) / 2
    best = None
    for name, s, e in host:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host event"


def idle_pct(tr: dict | None) -> float | None:
    """1 - busy / window of a reduced capture, in %; None without one."""
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
