"""From a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Kept from it: on each device plane (``/device:TPU:<n>``) the events of
its op line (``XLA Ops``), and on the host plane the benchmark's own
spans (``bench.*``), among them ``bench.window`` around the measured
window.  Everything is clipped to that window.  A reduced trace can be
written to and read from JSON, which is how the tests keep a recorded
one.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Iterable

OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str               # an op's HLO instruction name, or a span's
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceView:
    devices: dict            # device plane name -> [Event] of its ops
    host: list               # [Event] of the benchmark's host spans
    window: tuple            # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # -- serialisation -----------------------------------------------------

    def to_json(self) -> dict:
        def evs(xs):
            return [[e.name, e.start_ns, e.dur_ns] for e in xs]
        return {"window": list(self.window), "host": evs(self.host),
                "devices": {k: evs(v) for k, v in self.devices.items()}}

    @classmethod
    def from_json(cls, d: dict) -> "TraceView":
        def evs(xs):
            return [Event(*x) for x in xs]
        return cls(devices={k: evs(v) for k, v in d["devices"].items()},
                   host=evs(d["host"]), window=tuple(d["window"]))


def op_name(text: str) -> str:
    """The instruction name of a device op event, whose name is the HLO
    text (``%fused_flash_fwd.10 = (f32[...]) custom-call(...)``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(trace_dir: str) -> TraceView:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host, window = {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend(Event(op_name(e.name), float(e.start_ns),
                                     float(e.duration_ns))
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    ev = Event(e.name, float(e.start_ns),
                               float(e.duration_ns))
                    if e.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    else:
                        host.append(ev)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return TraceView(devices=devices, host=host, window=window)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def _clipped(events: Iterable[Event], window) -> list[tuple]:
    lo, hi = window
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: list[tuple]) -> list[tuple]:
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(view: TraceView) -> float:
    """Seconds of the window in which some op ran on the device, averaged
    over the devices."""
    if not view.devices:
        return 0.0
    tot = [sum(b - a for a, b in merge(_clipped(ops, view.window)))
           for ops in view.devices.values()]
    return sum(tot) / len(tot) * 1e-9


def matching_s(view: TraceView, match: Callable[[Event], bool]) -> float:
    """Device seconds of the ops that ``match`` selects, within the window,
    averaged over the devices."""
    if not view.devices:
        return 0.0
    tot = [sum(b - a for a, b in merge(
        _clipped([e for e in ops if match(e)], view.window)))
        for ops in view.devices.values()]
    return sum(tot) / len(tot) * 1e-9


def _overlap_ns(a: list[tuple], b: list[tuple]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def exposed_s(view: TraceView, match: Callable[[Event], bool]) -> float:
    """Device seconds of the ops that ``match`` selects during which no
    other op ran on the same device, within the window, averaged over
    the devices: the part of that work nothing hides."""
    if not view.devices:
        return 0.0
    tot = []
    for ops in view.devices.values():
        mine = merge(_clipped([e for e in ops if match(e)], view.window))
        rest = merge(_clipped([e for e in ops if not match(e)],
                              view.window))
        tot.append(sum(b - a for a, b in mine) - _overlap_ns(mine, rest))
    return sum(tot) / len(tot) * 1e-9


def prefix_matcher(names: Iterable[str]) -> Callable[[Event], bool]:
    """Selects the ops whose instruction name starts with one of
    ``names`` (``collective-permute`` matches
    ``collective-permute-start.3``)."""
    names = tuple(names)
    return lambda e: e.name.startswith(names)


def names_matcher(names: Iterable[str]) -> Callable[[Event], bool]:
    """Selects the ops whose instruction name is one of ``names`` with
    XLA's numeric suffix (``fused_flash_fwd`` matches
    ``fused_flash_fwd.10``)."""
    names = frozenset(names)
    return lambda e: e.name.split(".", 1)[0] in names


def top_ops(view: TraceView, k: int = 10) -> list[list]:
    """The ``k`` device ops that took most time in the window, summed over
    their launches and averaged over the devices: ``[[name, seconds]]``."""
    acc: dict[str, float] = {}
    n = max(len(view.devices), 1)
    for ops in view.devices.values():
        for e in ops:
            a = max(e.start_ns, view.window[0])
            b = min(e.end_ns, view.window[1])
            if b > a:
                acc[e.name] = acc.get(e.name, 0.0) + (b - a) * 1e-9 / n
    return [[name, s] for name, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(view: TraceView, k: int = 10) -> list[list]:
    """The ``k`` longest stretches of the window in which the first
    device ran nothing, each named by the host span that overlapped it
    most (``"none"`` where no span did): ``[[span, seconds]]``."""
    if not view.devices:
        return []
    dev = sorted(view.devices)[0]
    busy = merge(_clipped(view.devices[dev], view.window))
    gaps, t = [], view.window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if view.window[1] > t:
        gaps.append((t, view.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        best, name = 0.0, "none"
        for s in view.host:
            ov = min(b, s.end_ns) - max(a, s.start_ns)
            if ov > best:
                best, name = ov, s.name
        out.append([name, (b - a) * 1e-9])
    return out
