"""Reduction of a profiler trace to the benchmark's per-layer numbers.

A trace is read from the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``, into flat events ``(plane, line, name,
start_ns, duration_ns)``.  Device planes are those named ``/device:...``;
on a TPU their ``XLA Ops`` line holds one event per operation run (a
Pallas kernel under its ``name``) and their ``XLA Modules`` line one
event per program run (a jitted function as ``jit_<name>``).  Host
planes hold the dispatches JAX records and the spans the benchmark
opens around the calls into each layer.

The FW operation and byte counts, computed from shapes, also live here.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

FW_KERNELS = re.compile(r"fw_counts_vmem|fw_tiled_(diag|row_panel|col_panel"
                        r"|outer)")
WINDOW = "bench.window"          # host span around the measured window
# The VMEM-resident kernel's event, up to the shape of its first output.
_VMEM_SHAPE = re.compile(r"fw_counts_vmem[^=]* = \(f32\[([0-9,]+)\]")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# Elementwise operations of one Floyd-Warshall relaxation as the kernels'
# shared pivot update does them: add, multiply and clip of the candidate
# count; three compares; two mask ands and the tie and; the distance
# select; the tie select, add and select of the count; the count clip.
FW_OPS_PER_RELAXATION = 14


def fw_relaxations(vpad: int) -> int:
    """Relaxations of one placement's Floyd-Warshall: Vpad pivots, each
    over a Vpad x Vpad tile."""
    return vpad ** 3


def fw_ops(vpad: int) -> int:
    return FW_OPS_PER_RELAXATION * fw_relaxations(vpad)


def fw_hbm_bytes(vpad: int) -> int:
    """HBM bytes of one placement in the VMEM-resident kernel: the float32
    weights read once, distances and counts written once."""
    return 3 * 4 * vpad * vpad


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float      # ns
    end: float        # ns


def read_xplane(path: str) -> list[Event]:
    """Flat events of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns),
                                 float(e.start_ns) + float(e.duration_ns)))
    return out


def latest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class Reduction:
    """Device busy time, kernel and program time by name, and idle gaps
    named by host activity, inside the benchmark's window span."""

    def __init__(self, events: list[Event]):
        self.events = events
        dev = [e for e in events if e.plane.startswith("/device:")]
        self.devices = sorted({e.plane for e in dev})
        has_ops = {e.plane for e in dev if e.line == OPS_LINE}
        self.ops = [e for e in dev if e.line == OPS_LINE
                    or (e.plane not in has_ops and e.line == MODULES_LINE)]
        self.modules = [e for e in dev if e.line == MODULES_LINE]
        self.host = [e for e in events if not e.plane.startswith("/device:")]
        win = [e for e in self.host if e.name == WINDOW]
        if win:
            self.t0 = min(e.start for e in win)
            self.t1 = max(e.end for e in win)
        elif self.ops:
            self.t0 = min(e.start for e in self.ops)
            self.t1 = max(e.end for e in self.ops)
        else:
            self.t0 = self.t1 = 0.0

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Reduction | None":
        path = latest_xplane(trace_dir)
        return None if path is None else cls(read_xplane(path))

    def _clip(self, e: Event) -> float:
        return max(0.0, min(e.end, self.t1) - max(e.start, self.t0))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, plane: str):
        return _union((max(e.start, self.t0), min(e.end, self.t1))
                      for e in self.ops
                      if e.plane == plane and self._clip(e) > 0)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for d in self.devices
                  for a, b in self.busy_intervals(d))
        return tot * 1e-9 / len(self.devices)

    def op_s(self, pattern) -> float:
        """Device seconds of the operations whose name matches, per
        device."""
        rx = re.compile(pattern)
        tot = sum(self._clip(e) for e in self.ops if rx.search(e.name))
        return tot * 1e-9 / max(len(self.devices), 1)

    def fw_relaxations(self) -> int:
        """Relaxations the VMEM-resident FW kernel ran in the window, from
        each event's output shape f32[..., V, V]: rows x V^3."""
        tot = 0
        for e in self.ops:
            m = _VMEM_SHAPE.search(e.name)
            if m and self._clip(e) > 0:
                dims = [int(x) for x in m.group(1).split(",")]
                rows = 1
                for d in dims[:-2]:
                    rows *= d
                tot += rows * fw_relaxations(dims[-1])
        return tot

    def module_s(self, pattern) -> float:
        rx = re.compile(pattern)
        tot = sum(self._clip(e) for e in self.modules if rx.search(e.name))
        return tot * 1e-9 / max(len(self.devices), 1)

    def top_ops(self, n: int = 10):
        """[name, seconds] of the operations with the most self time (an
        op's time less that of the ops nested in it, such as a loop's
        body), by HLO instruction name."""
        by = {}
        for d in self.devices:
            evs = sorted((e for e in self.ops if e.plane == d),
                         key=lambda e: (e.start, -e.end))
            stack = []
            for e in evs:
                while stack and stack[-1][0].end <= e.start:
                    stack.pop()
                own = self._clip(e)
                if stack and e.end <= stack[-1][0].end:
                    stack[-1][1][0] -= own
                cell = [own]
                stack.append((e, cell))
                by.setdefault(op_name(e.name), []).append(cell)
        tot = {k: sum(c[0] for c in v) for k, v in by.items()}
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / max(len(self.devices), 1)] for k, v in top
                if v > 0]

    def idle_gaps(self, n: int = 10):
        """[name, seconds] of the longest stretches in which no operation
        ran on the first device, each named by what the host's Python
        thread was doing: the benchmark's layer span that overlaps the gap
        most (``driver`` where none does) and the innermost event that
        covers most of it (``untraced host work`` where no event covers
        half of it, such as numpy on the host)."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        edges = [self.t0] + [x for ab in busy for x in ab] + [self.t1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.sort(key=lambda ab: ab[0] - ab[1])
        lines = {(e.plane, e.line) for e in self.host if e.name == WINDOW}
        mine = [e for e in self.host
                if (e.plane, e.line) in lines and e.name != WINDOW]
        spans = [e for e in mine if e.name.startswith("bench.")]
        inner = [e for e in mine if not e.name.startswith("bench.")]
        out = []
        for a, b in gaps[:n]:
            span = _most(spans, a, b) or "driver"
            what = _most(inner, a, b, (b - a) / 2) or "untraced host work"
            out.append([f"{span}: {what}", (b - a) * 1e-9])
        return out


def op_name(hlo: str) -> str:
    """The instruction name of an op event (``%fusion.3 = f32[...] ...``
    -> ``fusion.3``)."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _most(events, a, b, least: float = 0.0):
    """Name of the event that overlaps [a, b] most, and by more than
    ``least`` ns, the shorter one on a tie (so an enclosing event yields to
    what it encloses)."""
    best = None
    for e in events:
        ov = min(e.end, b) - max(e.start, a)
        if ov > least:
            key = (ov, -(e.end - e.start))
            if best is None or key > best[0]:
                best = (key, e.name)
    return None if best is None else best[1]
