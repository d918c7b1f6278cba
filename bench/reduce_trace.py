"""Reduction of a JAX profiler trace to the quantities the per-layer
metrics read: device busy time, device time per program, gaps between
programs, and what the host was doing in each idle gap.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per operation run, their ``XLA Modules`` line one event per
program run, named ``jit_<function>(<fingerprint>)``.  Host threads are
lines of ``/host:CPU``; the harness's own spans (``bench.*``) and, where
the Python tracer ran, one event per Python call (``$file:line name``)
sit there.  Host and device events share one clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)        # (start_ns, end_ns, op)
    modules: list = field(default_factory=list)    # (start_ns, end_ns, name)


@dataclass
class Trace:
    devices: list
    host: list              # main thread: (start_ns, end_ns, name)

    # -- the traced window ------------------------------------------------
    def window(self) -> tuple[float, float]:
        """The span of the harness's ``bench.window`` annotation."""
        spans = [(s, e) for s, e, n in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return spans[0]

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    # -- device time --------------------------------------------------------
    def busy_s(self, device: Device) -> float:
        """Seconds of the window in which an operation ran on ``device``
        (the union of its op intervals, clipped to the window)."""
        lo, hi = self.window()
        return union_ns([(max(s, lo), min(e, hi)) for s, e, _ in device.ops
                         if e > lo and s < hi]) / 1e9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices."""
        return 1.0 - self.mean_busy_s() / self.window_s()

    def module_runs(self, prefix: str, device: Device | None = None) -> list:
        """(start_ns, end_ns) of every run, inside the window, of the
        programs whose name starts with ``prefix`` (``jit_fn(`` for the
        function ``fn``), on ``device`` or on the first device."""
        lo, hi = self.window()
        dev = device or self.devices[0]
        return sorted((s, e) for s, e, n in dev.modules
                      if n.startswith(prefix) and s >= lo and e <= hi)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` device operations that took most time in the window,
        summed over runs and devices: [[op, seconds], ...]."""
        lo, hi = self.window()
        tot: dict[str, float] = {}
        for d in self.devices:
            for s, e, op in d.ops:
                if s >= lo and e <= hi:
                    tot[op] = tot.get(op, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    # -- idle gaps ----------------------------------------------------------
    def idle_gaps(self, device: Device | None = None) -> list:
        """(start_ns, end_ns) of every stretch of the window in which no
        operation ran on ``device`` (default: the first)."""
        lo, hi = self.window()
        dev = device or self.devices[0]
        gaps, t = [], lo
        for s, e in merged([(s, e) for s, e, _ in dev.ops if e > lo and s < hi]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def gaps_by_host(self, n: int = 10) -> list:
        """Idle seconds of the first device by what the host's main thread
        was doing, largest first: [[host activity, seconds], ...].  The
        activity of a gap is the innermost main-thread event (a harness
        span, or a Python call where the Python tracer ran) open at the
        gap's midpoint."""
        gaps = self.idle_gaps()
        events = sorted((s, -e, name) for s, e, name in self.host
                        if name != WINDOW_SPAN and "profiler" not in name)
        tot: dict[str, float] = {}
        stack: list = []                       # nested open events
        i = 0
        for lo, hi in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = (lo + hi) / 2
            while i < len(events) and events[i][0] <= mid:
                s, neg_e, name = events[i]
                while stack and stack[-1][0] < s:
                    stack.pop()
                stack.append((-neg_e, name))
                i += 1
            while stack and stack[-1][0] < mid:
                stack.pop()
            k = stack[-1][1] if stack else "(no host span)"
            tot[k] = tot.get(k, 0.0) + (hi - lo) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merged(intervals))


def merged(intervals) -> list:
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction; keep the
    instruction's name (``%fusion.12``)."""
    return event_name.split(" = ", 1)[0]


def from_profile(pd) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.start_ns, e.end_ns, op_name(e.name))
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(e.start_ns, e.end_ns, e.name)
                                   for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                # the main thread is the one that holds the window's span
                if any(n == WINDOW_SPAN for _, _, n in events):
                    host = events
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[-1]))
    return Trace(devices=devices, host=host)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]))


def load_bytes(data: bytes) -> Trace:
    import jax
    return from_profile(jax.profiler.ProfileData.from_serialized_xspace(data))
