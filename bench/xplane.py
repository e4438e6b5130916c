"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
kernel time and idle gaps.

The traced window is the host span ``bench/window`` that the harness opens
after the profiler starts and closes after the window's last
``block_until_ready`` (``window_bounds`` when the profiler dropped it).
Within it:

  busy_s      union of the intervals in which an operation ran on a device
              (the ``XLA Ops`` line of each ``/device:`` plane), averaged
              over the devices
  window_s    the span's length
  kernel_s    summed device time of the operations whose name or metadata
              matches a pattern (a Pallas kernel's function name)
  modules     executions of a named program that start inside the window
  device_ops  device time by kind of operation (``op_kind``), largest first;
              loops and calls, which hold other operations, left out
  idle_gaps   gaps between busy intervals on the first device, summed by the
              harness span (``bench/...``) the host was in at the gap's
              middle; ``host: other`` where it was in none (the trainer's own
              code between steps); gaps under 50 us are summed apart
"""

from __future__ import annotations

import collections
import dataclasses
import re

WINDOW = "bench/window"
SHORT_GAP_NS = 50_000  # gaps shorter than this are summed, not attributed


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""  # the name and every string metadata value, for matching

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Plane:
    name: str
    lines: dict  # line name -> list[Event]


def load(path) -> list[Plane]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)))


def from_profile(data) -> list[Plane]:
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = []
            for e in line.events:
                text = " ".join([e.name] + [str(v) for _, v in e.stats if isinstance(v, str)])
                events.append(Event(e.name, float(e.start_ns), float(e.duration_ns), text))
            lines[line.name] = events
        planes.append(Plane(plane.name, lines))
    return planes


_CONTAINERS = ("while", "conditional", "call")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def op_kind(name: str) -> str:
    """The kind of an HLO operation from its text, e.g. ``%fusion.3 = f32[8]
    fusion(...), kind=kOutput`` is ``fusion:kOutput`` and a Pallas kernel
    ``custom-call:tpu_custom_call``.  A bare name is its own kind."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    depth, i = 0, 0
    for i, ch in enumerate(rest):  # skip the result shape, a tuple or not
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0].strip()
    extra = _TARGET.search(rest) if opcode == "custom-call" else _KIND.search(rest)
    return f"{opcode}:{extra.group(1)}" if extra else opcode


def _merge(intervals):
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def device_planes(planes) -> list[Plane]:
    return sorted(
        (p for p in planes if p.name.startswith("/device:") and "XLA Ops" in p.lines),
        key=lambda p: p.name,
    )


def host_spans(planes, prefix: str = "bench/") -> list[Event]:
    return [
        e
        for p in planes
        if p.name.startswith("/host:")
        for events in p.lines.values()
        for e in events
        if e.name.startswith(prefix)
    ]


def window_bounds(spans, devices) -> tuple[float, float]:
    """The traced window: the ``bench/window`` span; where the profiler
    dropped it (it opens just after the profiler starts), the first
    ``bench/pull`` to the end of ``bench/sync``; failing those, the first to
    the last device operation."""
    windows = [e for e in spans if e.name == WINDOW]
    if windows:
        return windows[0].start_ns, windows[0].end_ns
    pulls = [e.start_ns for e in spans if e.name == "bench/pull"]
    syncs = [e.end_ns for e in spans if e.name == "bench/sync"]
    if pulls and syncs:
        return min(pulls), max(syncs)
    ops = [e for p in devices for e in p.lines["XLA Ops"]]
    return min(e.start_ns for e in ops), max(e.end_ns for e in ops)


def reduce(planes, kernels: dict | None = None, module: str | None = None, top: int = 10) -> dict:
    """The window's device numbers; ``kernels`` maps a label to a regular
    expression over operation names, ``module`` names a program to count."""
    spans = host_spans(planes)
    devices = device_planes(planes)
    if not devices:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    lo, hi = window_bounds(spans, devices)
    busy, ops_time = [], collections.Counter()
    kernel_ns = collections.Counter()
    patterns = {k: re.compile(v) for k, v in (kernels or {}).items()}
    first_merged = None
    for plane in devices:
        clipped = []
        for e in plane.lines["XLA Ops"]:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t <= s:
                continue
            clipped.append((s, t))
            kind = op_kind(e.name)
            if kind not in _CONTAINERS:
                ops_time[kind] += t - s
            for label, pat in patterns.items():
                if pat.search(e.text):
                    kernel_ns[label] += t - s
        merged = _merge(clipped)
        busy.append(sum(t - s for s, t in merged))
        if first_merged is None:
            first_merged = merged
    n = len(devices)
    gaps = collections.Counter()
    inner = [e for e in spans if e.name != WINDOW]
    edges = [lo] + [x for iv in first_merged for x in iv] + [hi]
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        if t - s < SHORT_GAP_NS:
            gaps["device: between ops (< 50 us)"] += t - s
            continue
        mid = (s + t) / 2
        owner = [e for e in inner if e.start_ns <= mid <= e.end_ns]
        name = min(owner, key=lambda e: e.dur_ns).name if owner else "host: other"
        gaps[name] += t - s
    modules = 0
    if module:
        pat = re.compile(module)
        modules = sum(
            1
            for e in devices[0].lines.get("XLA Modules", [])
            if lo <= e.start_ns < hi and pat.search(e.text)
        )
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "kernel_s": {k: kernel_ns[k] / n / 1e9 for k in patterns},
        "modules": modules,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_time.most_common(top)],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(top)],
    }
