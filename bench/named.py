"""The traced window by the program's own names: device time of any kernel
or scope a reader asks for, and each idle gap by the span the step loop
was in.

It reads the profiler trace of the run's window that ``harness.run`` writes
under ``<checkout>/.bench_trace/<cell>/`` (the run's ``trace["path"]``),
over the same window (``xplane.window_bounds``), and adds what the program
names in it:

  op_s            device time of each distinct operation, by its HLO text
                  and op name; loops and calls, which hold other
                  operations, are not counted.  ``labelled_s`` sums it by
                  label: a kernel name in the operation's HLO text
                  (``flash_fwd``), or a scope in its op name (``adamw``;
                  forward and backward alike); ``kernel_ms`` and ``scope_ms``
                  read any label a reader asks for
  idle_by_span    each idle gap between device operations on the first
                  device, by the innermost harness (``bench/``) or program
                  (``train/``) span open at its midpoint on the step-loop
                  thread, the thread that holds ``bench/window`` (failing
                  that, ``train/step``); ``host: other`` where none is;
                  gaps under 50 us are summed apart, as in ``xplane.reduce``
  realize_idle_s  time of the gaps of 50 us or more whose midpoint lies in a
                  ``train/realize`` span of that thread; None where the
                  thread has none in the window

Host lines are kept apart by their position in the trace: the profiler
names a line after its OS thread, and threads of one name must not merge.
Spans of other threads (the prefetch producer's) never name a gap.
"""

from __future__ import annotations

import collections
import functools
import pathlib
import re

import xplane

LOOP_MARKS = (xplane.WINDOW, "train/step")  # what finds the step-loop thread
LOOP_PREFIXES = ("bench/", "train/")  # spans that may name a gap
REALIZE = "train/realize"
OTHER = "host: other"
SHORT = "device: between ops (< 50 us)"


# A kernel name matches as a whole word of an operation's HLO text
# (``%flash_fwd.3 = ...``); a scope as a whole word of its op name
# (``jit(train_step)/adamw/mul``, ``transpose(jvp(lm_loss))/dot_general``),
# never inside a longer name or a quoted parameter path.
@functools.lru_cache(maxsize=None)
def _label(name: str) -> re.Pattern:
    return re.compile(rf"(?<![\w']){re.escape(name)}(?![\w'])")


def from_profile(data) -> tuple[list[xplane.Plane], list[list[xplane.Event]]]:
    """The device planes as ``xplane.from_profile`` reads them, and every
    host line apart, in the trace's order."""
    host_lines = [
        [xplane.Event(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    return xplane.device_planes(xplane.from_profile(data)), host_lines


# The op name of a device operation (``jit(train_step)/adamw/mul``) is a
# stat of its event *metadata* (``tf_op``), which ``ProfileData`` does not
# expose; it is read from the serialized trace (an ``XSpace`` protocol
# buffer: planes 1; a plane's name 2, event metadata 4 and stat metadata 5,
# both maps of key 1 to value 2; event metadata name 2 and stats 5; stat
# metadata id 1 and name 2; a stat's metadata id 1, string 5, or reference
# 7 to the stat metadata whose name is the string).
OP_NAME_STAT = "tf_op"


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of the message ``buf[lo:hi]``: an int for a
    varint, a (start, end) span for a length-delimited field; fixed-width
    fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire} at byte {i}")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode(errors="replace")


def device_op_names(buf: bytes) -> dict[str, str]:
    """Operation text -> op name, for every operation of the device planes
    of the serialized trace ``buf`` whose metadata holds one."""
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        fields = list(_fields(buf, *plane)) if f == 1 else []
        if not any(k == 2 and _text(buf, v).startswith("/device:") for k, v in fields):
            continue
        maps = {4: [], 5: []}  # map entries' values: event and stat metadata
        for k, v in fields:
            if k in maps:
                maps[k] += [mv for mk, mv in _fields(buf, *v) if mk == 2]
        stat_names = {}
        for span in maps[5]:
            meta = dict(_fields(buf, *span))
            if 1 in meta and 2 in meta:
                stat_names[meta[1]] = _text(buf, meta[2])
        for span in maps[4]:
            name = op_name = None
            for k, v in _fields(buf, *span):
                if k == 2:
                    name = _text(buf, v)
                elif k == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        op_name = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7))
            if name and op_name:
                out[name] = op_name
    return out


def loop_line(host_lines) -> list[xplane.Event]:
    """The step-loop thread's events: the line that holds ``bench/window``,
    failing that the one with the most ``train/step`` spans; [] if none."""
    for mark in LOOP_MARKS:
        counts = [sum(e.name == mark for e in line) for line in host_lines]
        if counts and max(counts):
            return host_lines[counts.index(max(counts))]
    return []


def reduce(devices, host_lines, op_names: dict) -> dict:
    """``op_s``, ``idle_by_span`` and ``realize_idle_s`` of the window; an
    operation's op name comes from ``op_names`` (``device_op_names``)."""
    if not devices:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    loop = loop_line(host_lines)
    bench = [e for line in host_lines for e in line if e.name.startswith("bench/")]
    lo, hi = xplane.window_bounds(bench, devices)
    op_ns = collections.Counter()
    first = []
    for i, plane in enumerate(devices):
        for e in plane.lines["XLA Ops"]:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t <= s:
                continue
            if i == 0:
                first.append((s, t))
            if xplane.op_kind(e.name) in xplane._CONTAINERS:
                continue
            op_ns[e.text, op_names.get(e.name, "")] += t - s
    spans = [
        e for e in loop
        if e.name != xplane.WINDOW and e.name.startswith(LOOP_PREFIXES)
        and e.end_ns > lo and e.start_ns < hi
    ]
    realize = [e for e in spans if e.name == REALIZE]
    gaps = collections.Counter()
    realize_ns = 0.0
    edges = [lo] + [x for iv in xplane._merge(first) for x in iv] + [hi]
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        if t - s < xplane.SHORT_GAP_NS:
            gaps[SHORT] += t - s
            continue
        mid = (s + t) / 2
        owner = [e for e in spans if e.start_ns <= mid <= e.end_ns]
        gaps[min(owner, key=lambda e: e.dur_ns).name if owner else OTHER] += t - s
        if any(e.start_ns <= mid <= e.end_ns for e in realize):
            realize_ns += t - s
    n = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
        "idle_by_span": [[k, v / 1e9] for k, v in gaps.most_common()],
        "realize_idle_s": realize_ns / 1e9 if realize else None,
    }


def labelled_s(red: dict, kernels=(), scopes=()) -> dict:
    """Device time of the operations that carry each label: a name of
    ``kernels`` in an operation's HLO text, one of ``scopes`` in its op
    name; labels that name no operation are left out."""
    out = {}
    for label, in_text in [(k, True) for k in kernels] + [(k, False) for k in scopes]:
        pat = _label(label)
        found = []
        for (text, op_name), sec in red["op_s"].items():
            where = text if in_text else op_name
            if label in where and pat.search(where):
                found.append(sec)
        if found:
            out[label] = sum(found)
    return out


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData

    buf = pathlib.Path(path).read_bytes()
    return reduce(*from_profile(ProfileData.from_serialized_xspace(buf)), device_op_names(buf))


def for_run(run: dict) -> dict | None:
    """The reduction of the run's trace, cached per trace file; None for an
    untraced run."""
    path = (run.get("trace") or {}).get("path")
    if path is None:
        return None
    return _reduce_file(path, pathlib.Path(path).stat().st_mtime_ns)


def per_step_ms(run: dict, seconds: float | None) -> float | None:
    """``seconds`` of the traced window as milliseconds per traced step."""
    steps = run["trace"]["steps"] if run.get("trace") else 0
    if seconds is None or not steps:
        return None
    return 1000.0 * seconds / steps


def _labelled_ms(run: dict, kernels=(), scopes=()) -> float | None:
    red = for_run(run)
    if red is None:
        return None
    found = labelled_s(red, kernels, scopes)
    return per_step_ms(run, sum(found.values())) if found else None


def kernel_ms(run: dict, *names: str) -> float | None:
    """Device time per traced step of the operations whose HLO text holds
    any of the kernel ``names`` (``"flash_fwd"``, ``"expert_gmm"``); None
    where none of them names an operation."""
    return _labelled_ms(run, kernels=names)


def scope_ms(run: dict, *names: str) -> float | None:
    """As ``kernel_ms``, for scopes in the op name (``"adamw"``, ``"moe"``)."""
    return _labelled_ms(run, scopes=names)


def roofline(run: dict, peaks: dict, family: str) -> float | None:
    """Roofline share of a kernel family of the flops module: the least time
    its work (``trace["kernel_work"]``) allows on the chip, the larger of its
    operations over the bf16 peak and its bytes over the HBM bandwidth per
    step, over the family's device time (``trace["kernel_s"]``); None where
    no kernel of the family ran."""
    tr = run.get("trace")
    if not tr or not tr["kernel_s"].get(family):
        return None
    least = sum(
        max(work[family][0] / peaks["bf16_flops_per_s"], work[family][1] / peaks["hbm_bytes_per_s"])
        for work in tr["kernel_work"]
    )
    return 100.0 * least / tr["kernel_s"][family]
