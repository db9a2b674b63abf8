"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each TPU is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per operation run, and whose ``XLA Modules`` line holds one
event per compiled program run. Host threads are lines of ``/host:CPU``;
their events (Python calls, ``TraceAnnotation`` spans) say what the host
was doing. On the CPU backend, which has no device plane, the events that
carry an ``hlo_op`` stat stand for the device's operations: that is how the
tests exercise this reduction on a recorded trace.

Times are nanoseconds on the profiler's own clock, shared by all planes.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import warnings

import numpy as np

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[str, int, int]]        # (name, start_ns, end_ns)
    modules: List[Tuple[str, int, int]]
    # collectives in flight asynchronously (``Async XLA Ops``): the chip
    # may compute meanwhile, so they count as collective time, not busy.
    async_collectives: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class Summary:
    devices: List[Device]
    host: List[Tuple[str, int, int]]      # host spans, all threads
    window_s: float

    # -- busy and idle ---------------------------------------------------
    def busy_ns(self, dev: Device) -> int:
        return _union_len([(s, e) for _, s, e in dev.ops])

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([self.busy_ns(d) for d in self.devices])) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - min(self.busy_s / self.window_s, 1.0)

    # -- operations ----------------------------------------------------
    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose name matches ``pattern``,
        averaged over the chips."""
        rx = re.compile(pattern)
        per = [sum(e - s for n, s, e in d.ops if rx.search(n)) / 1e9
               for d in self.devices]
        return float(np.mean(per)) if per else 0.0

    def module_seconds(self, pattern: str) -> Tuple[float, int]:
        """(device seconds, runs) of compiled programs whose name matches,
        averaged over the chips."""
        rx = re.compile(pattern)
        secs, runs = [], []
        for d in self.devices:
            hits = [(s, e) for n, s, e in d.modules if rx.search(n)]
            secs.append(sum(e - s for s, e in hits) / 1e9)
            runs.append(len(hits))
        if not secs:
            return 0.0, 0
        return float(np.mean(secs)), int(np.mean(runs))

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective ran and no other operation did,
        averaged over the chips."""
        per = []
        for d in self.devices:
            coll = [(s, e) for n, s, e in d.ops + d.async_collectives
                    if COLLECTIVE.search(n)]
            other = [(s, e) for n, s, e in d.ops if not COLLECTIVE.search(n)]
            per.append(_union_len(coll) - _overlap_len(coll, other))
        return float(np.mean(per)) / 1e9 if per else 0.0

    # -- the breakdown of the result line ----------------------------------
    def device_ops(self, top: int = 10) -> List[list]:
        totals: Dict[str, float] = {}
        for d in self.devices:
            for n, s, e in d.ops:
                totals[n] = totals.get(n, 0.0) + (e - s) / 1e9
        k = max(len(self.devices), 1)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        return [[n, v / k] for n, v in ranked]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """Idle time on the first chip, summed by what the host was doing
        in the middle of each gap (the innermost host span that covers
        it), longest first."""
        if not self.devices:
            return []
        busy = _merge([(s, e) for _, s, e in self.devices[0].ops])
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
        spans = sorted(self.host, key=lambda h: h[1])
        starts = np.array([h[1] for h in spans]) if spans else np.array([])
        totals: Dict[str, float] = {}
        for s, e in gaps:
            mid = (s + e) // 2
            label = "host: no span"
            best = None
            hi = int(np.searchsorted(starts, mid, side="right"))
            for name, hs, he in spans[max(0, hi - 400):hi]:
                if hs <= mid <= he and (best is None or he - hs < best):
                    best, label = he - hs, name
            totals[label] = totals.get(label, 0.0) + (e - s) / 1e9
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        return [[n, v] for n, v in ranked]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _union_len(iv) -> int:
    return int(sum(e - s for s, e in _merge(iv)))


def _overlap_len(a, b) -> int:
    """Length of union(a) intersected with union(b)."""
    ma, mb = _merge(a), _merge(b)
    i = j = total = 0
    while i < len(ma) and j < len(mb):
        lo, hi = max(ma[i][0], mb[j][0]), min(ma[i][1], mb[j][1])
        if hi > lo:
            total += hi - lo
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return int(total)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _stat(event, key):
    # Reading an event's stats warns under the installed jaxlib; harmless.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for k, v in event.stats:
            if k == key:
                return v
    return None


def reduce(path: str, window_s: float, chips: int) -> Summary:
    """Read one ``.xplane.pb`` into a :class:`Summary` of its first
    ``chips`` devices."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, cpu_ops = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops = [(_short(e.name), int(e.start_ns),
                    int(e.start_ns + e.duration_ns))
                   for e in lines["XLA Ops"].events]
            mods = ([(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for e in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [])
            asyncs = ([(_short(e.name), int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in lines["Async XLA Ops"].events
                       if COLLECTIVE.search(_short(e.name))]
                      if "Async XLA Ops" in lines else [])
            devices.append(Device(plane.name, ops, mods, asyncs))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                    if line.name.startswith("tf_XLA"):
                        if _stat(e, "hlo_op") is not None:
                            cpu_ops.append(span)
                    elif e.duration_ns > 0:
                        host.append(span)
    devices.sort(key=lambda d: _device_index(d.name))
    if not devices and cpu_ops:
        devices = [Device("/host:CPU (XLA ops)", cpu_ops, [])]
    return Summary(devices=devices[:chips], host=host, window_s=window_s)


def _short(name: str) -> str:
    """An XLA op's instruction name: the device trace names each op by its
    whole HLO text, ``%fusion.12 = f32[...] fusion(...), ...``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0
