"""From a profiler trace to numbers: pure arithmetic over lists of events,
with no JAX and no file reading (that is `device.read_trace`), so that a
test pins it on a synthetic list.

An event is `(name, start, end)` on one clock, in any one unit (the trace's
nanoseconds); `seconds_per_unit` converts at the end. The input of `reduce`:

    {"window": (lo, hi) or None,
     "devices": {"/device:TPU:0": {"modules": [event, ...], "ops": [...],
                                   "stacks": [event, ...]}},
     "spans": [event, ...],        # the benchmark's own TraceAnnotations
     "activities": [event, ...]}   # what the host runtime traced itself

`stacks` (optional) holds the same device operations as `ops`, each named by
the name stack the profile carries for it (`jit(f)/while/body/<scope>/.../
<primitive>:`) in place of its HLO name, or by "" where it carries none.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

#: gaps shorter than this are summed under one name instead of attributed
SHORT_GAP_S = 20e-6


#: segments of a name stack that are frames of the tracing machinery and no
#: scope anyone named: a transformation with its argument (`jit(run_span)`,
#: `vmap(...)`) and the control-flow frames
_FRAME = re.compile(r"^(?:[A-Za-z_]\w*\(.*\)|while|body|cond|pjit|"
                    r"body_fun|cond_fun|branch_\d+_fun|closed_call|"
                    r"checkpoint|remat|custom_jvp_call|custom_vjp_call)$")


def union(intervals):
    """Sorted, disjoint cover of `(start, end)` intervals."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def clip_events(events, lo, hi):
    """Named events cut to [lo, hi]; those outside it are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def covered(merged) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged, lo, hi):
    """What a disjoint sorted cover leaves open inside [lo, hi]."""
    out, at = [], lo
    for start, end in clip(merged, lo, hi):
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(merged, lo, hi) -> float:
    """Length of [lo, hi] that a disjoint sorted cover covers."""
    i = bisect.bisect_right(merged, (lo, float("inf"))) - 1
    total = 0.0
    for start, end in merged[max(i, 0):]:
        if start >= hi:
            break
        total += max(0.0, min(end, hi) - max(start, lo))
    return total


def self_times(events):
    """name -> time inside events of that name and inside none of their
    children. Events of one device line nest (a `while` holds its body's
    fusions), so a plain sum would count the body twice."""
    out = defaultdict(float)
    stack = []  # (name, end)
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(end, stack[-1][1]) - start
        out[name] += end - start
        stack.append((name, end))
    return dict(out)


def scopes_of(stack: str) -> list:
    """The named scopes of a name stack, outermost first: its segments less
    the frames (`_FRAME`) and less the last one, which is the primitive the
    operation was traced from (with the profile's `:<type>` after it) and
    no scope. A scope that encloses itself counts once."""
    segments = [seg for seg in stack.split(":")[0].split("/")[:-1]
                if seg and not _FRAME.match(seg)]
    return list(dict.fromkeys(segments))


def scope_times(stacks):
    """scope -> device time under it: the self time (`self_times`) of every
    operation whose name stack holds the scope, at whatever depth. So the
    key holds a TOTAL: an operation under `a/b` counts under `a` and under
    `b`, and two scopes of which one encloses the other must not be added.
    Keyed by the scope's own name, not its path, so that a scope keeps its
    key when the code around it moves."""
    out = defaultdict(float)
    for stack, t in self_times(stacks).items():
        for scope in scopes_of(stack):
            out[scope] += t
    return dict(out)


def _owner(gap, named_covers):
    """The name whose cover overlaps the gap most, and that overlap."""
    best, best_len = None, 0.0
    for name, cover in named_covers.items():
        got = overlap(cover, *gap)
        if got > best_len:
            best, best_len = name, got
    return best, best_len


def attribute_gaps(idle, spans, activities, short=0.0):
    """label -> idle time, the label saying what the host was doing:
    `<benchmark span>/<host activity>`. A gap belongs to the benchmark span
    that overlaps it most (`no_span` if none does) and to the host activity
    that covers at least half of it (`python` if none does: interpreter
    code the runtime does not trace)."""
    def by_name(evs):
        return {
            name: union((s, e) for n, s, e in evs if n == name)
            for name in {n for n, _, _ in evs}
        }

    span_covers, activity_covers = by_name(spans), by_name(activities)
    out = defaultdict(float)
    for gap in idle:
        length = gap[1] - gap[0]
        if length < short:
            out["short_gaps/unattributed"] += length
            continue
        span, _ = _owner(gap, span_covers)
        activity, act_len = _owner(gap, activity_covers)
        if act_len < length / 2:
            activity = "python"
        out[f"{span or 'no_span'}/{activity}"] += length
    return dict(out)


def module_of(modules):
    """A lookup from an instant to the name of the module execution that
    holds it (`modules` are one device's, which do not overlap)."""
    ordered = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in ordered]

    def find(at):
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and ordered[i][2] >= at:
            return ordered[i][0]
        return "no_module"

    return find


def top(table, k=10):
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:k]
    return [[name, value] for name, value in rows if value > 0]


def reduce(events, seconds_per_unit=1e-9):
    """The traced stretch as numbers: `window_s`; `busy_s`, the time in
    which an operation ran on the device, averaged over the devices;
    `modules`, name -> [executions, device seconds] summed over the
    devices; `scopes`, name -> device seconds under that named scope
    (`scope_times`), summed over the devices, every one of them; and the
    two top-ten lists of the result line's `breakdown`."""
    devices = events["devices"]
    if not devices:
        return None
    everything = [e for d in devices.values() for e in d["ops"] + d["modules"]]
    lo, hi = events.get("window") or (
        min(e[1] for e in everything), max(e[2] for e in everything)
    )
    busy, modules = [], defaultdict(lambda: [0, 0.0])
    op_self, idle = defaultdict(float), defaultdict(float)
    scopes = defaultdict(float)
    for dev in devices.values():
        ops = clip_events(dev["ops"], lo, hi)
        cover = union((s, e) for _, s, e in ops)
        busy.append(covered(cover))
        for name, start, end in dev["modules"]:
            if start >= lo and end <= hi:
                modules[name][0] += 1
                modules[name][1] += (end - start) * seconds_per_unit
        find = module_of(dev["modules"])
        named = [(f"{find(s)}/{n}", s, e) for n, s, e in ops]
        for name, t in self_times(named).items():
            op_self[name] += t * seconds_per_unit
        for name, t in scope_times(
                clip_events(dev.get("stacks", ()), lo, hi)).items():
            scopes[name] += t * seconds_per_unit
        # a hole inside a running program is the program's, not the host's
        running = union(cover + clip(
            [(s, e) for _, s, e in dev["modules"]], lo, hi))
        between = gaps(running, lo, hi)
        idle["device/in_program_bubble"] += (
            covered(running) - covered(cover)) * seconds_per_unit
        for label, t in attribute_gaps(
            between, events["spans"], events["activities"],
            short=SHORT_GAP_S / seconds_per_unit,
        ).items():
            idle[label] += t * seconds_per_unit
    return {
        "window_s": (hi - lo) * seconds_per_unit,
        "busy_s": sum(busy) / len(busy) * seconds_per_unit,
        "devices": len(busy),
        "modules": dict(modules),
        "scopes": dict(scopes),
        "device_ops": top(op_self),
        "idle_gaps": top(idle),
    }
