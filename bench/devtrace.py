"""Reduction of a JAX profiler trace to what the device did.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
operations each TPU ran (plane ``/device:TPU:<n>``, line ``XLA Ops``)
and the host's annotated activity, both in seconds on the profiler's
one clock.  The rest works on those events: the union of busy
intervals, a kernel's time, the operations that took most time, and the
idle gaps by what the host was doing meanwhile.

On a TPU v5e with jax 0.9 a device operation's event name is its whole
HLO instruction (``%_run.77 = bf16[8,16,1,64]{...} custom-call(s32[8,128]
...``), and every Pallas kernel's instruction is named ``_run``: no
kernel's own name reaches the trace.  A kernel is therefore found by
the shape of its instruction (``kernel_events``), and a control-flow
operation (``while``) is an event that contains the events of its body.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

from stats import union_seconds

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host events that say nothing about what the host was doing
_HOST_NOISE = ("ThreadpoolListener", "end: ")
# longer host events are context (a whole window), not what the host did
_LONGEST_HOST_EVENT = 1.0


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the profiler's clock
    end: float
    where: str  # device plane, or host thread (line) name
    stats: tuple = ()  # (key, value) pairs as the profiler records them

    @property
    def dur(self) -> float:
        return self.end - self.start

    def text(self) -> str:
        """The name and every string statistic: where a kernel's name
        can appear (an op's own name, its HLO name or its long name)."""
        return " ".join([self.name] + [str(v) for _, v in self.stats
                                       if isinstance(v, str)])


@dataclasses.dataclass
class Trace:
    device: dict[str, list[Event]]  # device plane -> its operations
    host: list[Event]


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line, plane.name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in _events(line, line.name)
                            if not e.name.startswith(_HOST_NOISE))
    for ops in device.values():
        ops.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(device=device, host=host)


def _events(line, where: str) -> list[Event]:
    out = []
    for e in line.events:
        t = e.start_ns * 1e-9
        stats = tuple(sorted((str(k), v) for k, v in dict(e.stats).items()))
        out.append(Event(e.name, t, t + e.duration_ns * 1e-9, where, stats))
    return out


def busy_seconds(ops: list[Event]) -> float:
    """Seconds in which at least one operation ran."""
    return union_seconds((e.start, e.end) for e in ops)


def kernel_events(ops: list[Event], pattern: str) -> list[Event]:
    """Operations whose instruction matches the regular expression
    ``pattern`` (a kernel's output and operands as the trace shows them)."""
    rx = re.compile(pattern)
    return [e for e in ops if rx.search(e.name)]


def leaves(ops: list[Event]) -> list[Event]:
    """The operations that contain no other (a ``while`` holds its body's
    operations inside its own interval)."""
    ops = sorted(ops, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= e.end or nxt.end > e.end]


_INSTR = re.compile(r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = (\S+)")


def op_kind(e: Event) -> str:
    """An operation's instruction name without its number (``copy``,
    ``constant_dynamic-slice_fusion``); a Pallas kernel (``_run``) with
    its output type, which tells the kernels apart."""
    m = _INSTR.match(e.name)
    if m is None:
        return e.name
    name, out = m.group(1), m.group(2)
    if "custom-call" in e.name:
        return f"{name} -> {out.split('{')[0]}"
    return name


def top_ops(ops: list[Event], n: int = 10) -> list[list]:
    """The ``n`` kinds of operation that took most device time, counting
    each moment once (leaf operations only): [kind, seconds]."""
    by = defaultdict(float)
    for e in leaves(ops):
        by[op_kind(e)] += e.dur
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: list[Event], host: list[Event], t0: float, t1: float,
              n: int = 10) -> list[list]:
    """Idle device time in [t0, t1], summed by what the host was doing
    in each gap (the host event overlapping it most, or ``none``)."""
    gaps, end = [], t0
    for e in sorted(ops, key=lambda e: e.start):
        if e.start > end:
            gaps.append((end, min(e.start, t1)))
        end = max(end, e.end)
        if end >= t1:
            break
    if end < t1:
        gaps.append((end, t1))
    host = sorted(host, key=lambda h: h.start)
    starts = [h.start for h in host]
    by = defaultdict(float)
    for a, b in gaps:
        if b <= a:
            continue
        best, best_overlap = "none", 0.0
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and starts[i] > a - _LONGEST_HOST_EVENT:
            h = host[i]
            overlap = min(b, h.end) - max(a, h.start)
            if overlap > best_overlap and h.dur <= _LONGEST_HOST_EVENT:
                best, best_overlap = h.name, overlap
            i -= 1
        by[best] += b - a
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
