"""Profiler trace → the few event lists the per-layer metrics read.

``load(path)`` reads one ``.xplane.pb`` with JAX's own ``ProfileData``
and keeps, per device plane, the executions of whole programs (the
``XLA Modules`` line) and of single operations (the ``XLA Ops`` line),
plus the host's events and the benchmark's call spans.  Times stay in the
trace's nanoseconds; both clocks share the trace's time base.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

from harness import names

#: (name, start_ns, end_ns)
Event = Tuple[str, int, int]


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]          # host events other than the call spans
    calls: List[Tuple[int, int]]

    def window(self) -> Tuple[int, int]:
        return self.calls[0][0], self.calls[-1][1]


def find(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, device_prefix: str = "/device:") -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, calls = [], [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith(device_prefix):
            if "XLA Modules" not in lines and "XLA Ops" not in lines:
                continue
            devices.append(Device(
                name=plane.name,
                modules=_events(lines["XLA Modules"])
                if "XLA Modules" in lines else [],
                ops=_events(lines["XLA Ops"]) if "XLA Ops" in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in _events(ln):
                    if ev[0] == names.CALL_SPAN:
                        calls.append(ev[1:])
                    else:
                        host.append(ev)
    devices.sort(key=lambda d: d.name)
    calls.sort()
    return Trace(devices=devices, host=host, calls=calls)


def clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: List[Event]) -> List[Tuple[int, int]]:
    """Merged busy intervals of a list of events."""
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(clip(dev.ops or dev.modules, lo, hi)))


def device_ops(tr: Trace, top: int = 10) -> List[List]:
    """Operations that took most device time (seconds, mean per chip)."""
    tot: Dict[str, int] = {}
    lo, hi = tr.window()
    for dev in tr.devices:
        for n, s, e in clip(dev.ops or dev.modules, lo, hi):
            tot[n] = tot.get(n, 0) + (e - s)
    k = max(len(tr.devices), 1)
    best = sorted(tot.items(), key=lambda x: -x[1])[:top]
    return [[n, v / k / 1e9] for n, v in best]


def idle_gaps(tr: Trace, top: int = 10) -> List[List]:
    """Longest idle gaps of the first device, each named by the host
    event that overlaps it most (what the host was doing meanwhile)."""
    if not tr.devices:
        return []
    lo, hi = tr.window()
    busy = union(clip(tr.devices[0].ops or tr.devices[0].modules, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:top]:
        # the host event covering most of the gap; the innermost on ties
        best = max(((min(e, g1) - max(s, g0), -(e - s), n)
                    for n, s, e in tr.host), default=(0, 0, ""))
        label = "host: " + best[2] if best[0] > 0 else "host: no traced event"
        out.append([label, (g1 - g0) / 1e9])
    return out


def modules_named(dev: Device, key: str, lo: int, hi: int) -> List[Event]:
    """Executions of the programs whose name holds ``key`` in [lo, hi]."""
    return sorted((ev for ev in dev.modules
                   if key in ev[0] and ev[1] >= lo and ev[2] <= hi),
                  key=lambda x: x[1])
