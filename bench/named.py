"""The traced window by the program's own names: device time of the flash
passes and the model's parts, and each idle gap by the span the step loop
was in.

It reads the profiler trace of the run's window that ``harness.run`` writes
under ``<checkout>/.bench_trace/<cell>/``, over the same window
(``xplane.window_bounds``), and adds what the program names in it:

  named_s         device time of the operations that carry a label: a
                  flash pass's kernel name (``flash_fwd``, ``flash_dq``,
                  ``flash_dkv``) in the operation's HLO text, or a model
                  part's scope (``attention``, ``mlp``, ``lm_loss``,
                  ``adamw``; forward and backward alike) in its op name;
                  labels that name no operation are left out; loops and
                  calls, which hold other operations, are not counted
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

TRACE_ROOT = pathlib.Path(__file__).resolve().parent.parent / ".bench_trace"
LOOP_MARKS = (xplane.WINDOW, "train/step")  # what finds the step-loop thread
LOOP_PREFIXES = ("bench/", "train/")  # spans that may name a gap
REALIZE = "train/realize"
OTHER = "host: other"
SHORT = "device: between ops (< 50 us)"

# A kernel name matches as a whole word of an operation's HLO text
# (``%flash_fwd.3 = ...``); a scope as a whole word of its op name
# (``jit(train_step)/adamw/mul``, ``transpose(jvp(lm_loss))/dot_general``),
# never inside a longer name or a quoted parameter path.
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
SCOPES = ("attention", "mlp", "lm_loss", "adamw")
LABELS = {n: re.compile(rf"(?<![\w']){n}(?![\w'])") for n in KERNELS + SCOPES}


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
    """``named_s``, ``idle_by_span`` and ``realize_idle_s`` of the window; a
    kernel name is looked for in an operation's text, a scope in its op name
    (``op_names``, from ``device_op_names``)."""
    if not devices:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    loop = loop_line(host_lines)
    bench = [e for line in host_lines for e in line if e.name.startswith("bench/")]
    lo, hi = xplane.window_bounds(bench, devices)
    named_ns = collections.Counter()
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
            op_name = op_names.get(e.name, "")
            for label, pat in LABELS.items():
                text = e.text if label in KERNELS else op_name
                if label in text and pat.search(text):
                    named_ns[label] += t - s
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
        "named_s": {k: v / n / 1e9 for k, v in named_ns.items()},
        "idle_by_span": [[k, v / 1e9] for k, v in gaps.most_common()],
        "realize_idle_s": realize_ns / 1e9 if realize else None,
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData

    buf = pathlib.Path(path).read_bytes()
    return reduce(*from_profile(ProfileData.from_serialized_xspace(buf)), device_op_names(buf))


def for_run(run: dict) -> dict | None:
    """The reduction of the run's trace: the newest one under
    ``TRACE_ROOT``, which the harness wrote for this run; None for an
    untraced run."""
    if not run.get("trace"):
        return None
    paths = sorted(TRACE_ROOT.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime_ns)
    if not paths:
        return None
    return _reduce_file(str(paths[-1]), paths[-1].stat().st_mtime_ns)


def per_step_ms(run: dict, seconds: float | None) -> float | None:
    """``seconds`` of the traced window as milliseconds per traced step."""
    steps = run["trace"]["steps"] if run.get("trace") else 0
    if seconds is None or not steps:
        return None
    return 1000.0 * seconds / steps


def named_ms(run: dict, *labels: str) -> float | None:
    """Device time per traced step of the operations carrying any of
    ``labels``; None where none of them names an operation."""
    red = for_run(run)
    if red is None:
        return None
    found = [red["named_s"][k] for k in labels if k in red["named_s"]]
    return per_step_ms(run, sum(found)) if found else None
