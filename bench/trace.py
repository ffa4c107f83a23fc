"""Reduce a profiler trace of the measured window to device numbers.

The run wraps its window in a host annotation named ``bench.window`` and
records it with the JAX profiler (Python tracer off). This module reads
the ``.xplane.pb`` the profiler wrote with nothing but JAX's own reader
and returns, per device plane:

* every device operation as ``(key, start_ns, self_ns)``, the key being
  ``<program>:<op>`` with numeric suffixes stripped
  (``jit_build_fragment_table:fusion``; a Pallas kernel's op is its
  kernel name, ``bm25_resident_score_topk_db``). Ops nest (a ``while``
  spans its body's ops), so each op counts its self time: its interval
  inside the window less its children's;
* busy time: the union of operation intervals inside the window.

Idle gaps are the holes in that union, each named by the shortest host
event that spans its middle (what the host was doing then). The window's
length is the host annotation's, so the idle share needs no alignment of
device and host clocks; gap names do, and are ``unaligned`` where the two
clocks do not overlap.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
_OPS_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)


def _base(name: str) -> str:
    """``%fusion.78 = s32[...] fusion(...)`` -> ``fusion``;
    ``jit_build_fragment_table(1499...)`` -> ``jit_build_fragment_table``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.:]\d+$", "", name)


@dataclass
class DeviceTrace:
    window_s: float
    ops: dict = field(default_factory=dict)     # plane -> [(key, s, d)]
    busy_s: float = 0.0                         # mean over device planes
    gaps: list = field(default_factory=list)    # [(label, seconds)]

    def seconds(self, pattern: str) -> float:
        """Device seconds (self time) of ops whose key matches ``pattern``
        (regex), summed over device planes and averaged over them."""
        rx = re.compile(pattern)
        total = sum(d for evs in self.ops.values() for k, _, d in evs
                    if rx.search(k))
        return total / 1e9 / max(len(self.ops), 1)

    def op_seconds(self) -> float:
        """Device seconds of every op, averaged over device planes."""
        return self.seconds(".")

    def breakdown(self, n: int = 10) -> dict:
        per = defaultdict(float)
        for evs in self.ops.values():
            for k, _, d in evs:
                per[k] += d / 1e9 / len(self.ops)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(log_dir: str) -> str | None:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_file(path: str) -> DeviceTrace | None:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        return reduce_profile(ProfileData.from_serialized_xspace(f.read()))


def reduce_profile(pd) -> DeviceTrace | None:
    host_events, window = [], None
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.duration_ns > 0:
                        host_events.append((ev.start_ns, ev.duration_ns,
                                            ev.name))
    if window is None:
        return None
    trace = DeviceTrace(window_s=(window[1] - window[0]) / 1e9)
    busy, gaps = [], []
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        ops_line = next((lines[n] for n in _OPS_LINES if n in lines), None)
        if ops_line is None:
            continue
        mod_line = next((lines[n] for n in _MODULE_LINES if n in lines),
                        None)
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          _base(ev.name))
                         for ev in (mod_line.events if mod_line else ()))
        evs = [(ev.start_ns, ev.duration_ns, ev.name, dict(ev.stats))
               for ev in ops_line.events]
        if not evs:
            continue
        lo = min(s for s, *_ in evs)
        hi = max(s + d for s, d, *_ in evs)
        # device and host clocks share a time base when the device ops
        # overlap the host window; otherwise keep every op of the trace
        aligned = lo < window[1] and hi > window[0]
        w0, w1 = (window if aligned else (lo, hi))
        ops, spans, stack, j = [], [], [], 0
        for s, d, name, stats in sorted(evs, key=lambda e: (e[0], -e[1])):
            if s + d <= w0 or s >= w1:
                continue
            s0, s1 = max(s, w0), min(s + d, w1)
            mod = stats.get("hlo_module")
            if mod is None:
                while j < len(modules) and modules[j][1] <= s:
                    j += 1
                mod = (modules[j][2] if j < len(modules)
                       and modules[j][0] <= s else "?")
            while stack and spans[stack[-1]][1] <= s0:
                stack.pop()
            if stack:                    # nested: not the parent's self
                parent = stack[-1]
                ops[parent][2] -= min(s1, spans[parent][1]) - s0
            ops.append([f"{_base(str(mod))}:{_base(name)}", s0, s1 - s0])
            spans.append((s0, s1))
            stack.append(len(ops) - 1)
        trace.ops[plane.name] = [tuple(o) for o in ops]
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) // 2, aligned))
    if not busy:
        return trace
    trace.busy_s = sum(busy) / len(busy) / 1e9
    gaps.sort(reverse=True)
    host_events.sort()
    for length, mid, aligned in gaps[:10]:
        label = "unaligned"
        if aligned:
            spans = [(d, n) for s, d, n in host_events if s <= mid < s + d]
            label = min(spans)[1] if spans else "none"
        trace.gaps.append((label, length / 1e9))
    return trace
