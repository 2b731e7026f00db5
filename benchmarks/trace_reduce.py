"""From a profiler trace (``.xplane.pb``) to what the per-layer readers
use: per device the operations' intervals, the busy union, the idle gaps
and totals by operation name.  Reads with nothing but JAX.

A device is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed HLO operation and ``Async XLA Ops`` the
asynchronous ones (copies, collectives between start and done).  Busy
time is the union of the ``XLA Ops`` intervals, so nested events (a loop
and its body) count once.  The traced window of a device runs from its
first operation's start to its last one's end: the driver starts the
profiler on a running loop and stops it on one, so this is the steady
stretch, on the device's own clock, with no host clock to align.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def short_name(name: str) -> str:
    """``%fusion.5 = f32[..] fusion(...)`` -> ``fusion.5``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b):
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


@dataclass
class Device:
    index: int
    ops: list = field(default_factory=list)        # (name, start, end) ns
    async_ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)

    @property
    def window(self):
        if not self.ops:
            return (0.0, 0.0)
        return (min(o[1] for o in self.ops), max(o[2] for o in self.ops))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    @property
    def busy_s(self) -> float:
        return covered((s, e) for _, s, e in self.ops) / 1e9

    def gaps(self):
        """[(start, end)] in which no operation ran, inside the window."""
        u = union((s, e) for _, s, e in self.ops)
        return [(a[1], b[0]) for a, b in zip(u, u[1:]) if b[0] > a[1]]


@dataclass
class Trace:
    devices: list

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def window_s(self) -> float:
        return max(d.window_s for d in self.devices)

    def idle_pct_worst(self) -> float:
        return max(100.0 * (1.0 - d.busy_s / d.window_s) for d in self.devices)

    def op_totals(self):
        """{short name: seconds}, summed over events and averaged over the
        devices (a data-parallel trace mirrors one step on every chip)."""
        tot = {}
        for d in self.devices:
            for n, s, e in d.ops:
                k = short_name(n)
                tot[k] = tot.get(k, 0.0) + (e - s) / 1e9
        return {k: v / len(self.devices) for k, v in tot.items()}

    def steps(self) -> int:
        """Executions of the most frequent module on the busiest device."""
        best = 0
        for d in self.devices:
            count = {}
            for n, _, _ in d.modules:
                count[n] = count.get(n, 0) + 1
            best = max(best, max(count.values(), default=0))
        return best

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_totals().items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            gaps += [(e - s) / 1e9 for s, e in d.gaps()]
        gaps = sorted(gaps, reverse=True)[:top]
        return dict(
            device_ops=[[k, v] for k, v in ops],
            # what the host was doing in a gap is unknown until the
            # program's spans are on the profiler's clock
            idle_gaps=[["unknown", g] for g in gaps],
        )


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(find_xplane(path))
    devices = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = Device(index=int(m.group(1)))
        for line in plane.lines:
            target = {OPS_LINE: dev.ops, ASYNC_LINE: dev.async_ops,
                      MODULES_LINE: dev.modules}.get(line.name)
            if target is None:
                continue
            for ev in line.events:
                target.append(
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns))
                )
        if dev.ops:
            devices.append(dev)
    if not devices:
        raise ValueError(
            "the trace holds no device plane with operations: planes "
            + ", ".join(p.name for p in profile.planes)
        )
    return Trace(devices=sorted(devices, key=lambda d: d.index))
