"""The benchmark's side of the device: what it is, its published peaks,
what was compiled when, its memory peak, and reading a profiler trace into
the plain event lists that `trace_reduce.reduce` takes."""

from __future__ import annotations

import glob
import os
import re

#: Published peaks of one chip, keyed by the `device_kind` JAX reports.
#: The yardstick's own table: it may not move with the program's
#: (`observability/profiler._DEVICE_PEAKS`). An unlisted kind is an error.
PEAKS = {
    "TPU v5 lite": {
        "bytes_per_s": 819e9,
        "flops_bf16": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2e at "
                  "819 GB/s, 197 TFLOP/s bf16 per chip",
    },
}

#: prefix of the benchmark's own TraceAnnotations in a trace
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "traced"

CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def describe(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {kind!r} in "
            f"benchmark/device.py (known: {sorted(PEAKS)})"
        )
    return PEAKS[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no allocator statistics, as the CPU's does not)."""
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    )


class CompileCounter:
    """Counts, per phase of the run, what JAX compiled: persistent-cache
    misses (an executable compiled and written because the cache did not
    hold it) and backend compile-or-load events (every new executable,
    cached or not). Register before the first jit."""

    def __init__(self):
        self.phase = "setup"
        self.cache_misses = {}
        self.compiles = {}

    def register(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.cache_misses[self.phase] = (
                self.cache_misses.get(self.phase, 0) + 1
            )

    def _on_duration(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.compiles[self.phase] = self.compiles.get(self.phase, 0) + 1


# ---------------------------------------------------------------- the trace

def newest_xplane(trace_dir: str):
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


_OP_TEXT = re.compile(r"^%?([^ =]+) = (?:\()?([a-z0-9]+\[[^\]]*\])?")


def short_op(text: str) -> str:
    """`%fusion.168 = f32[1560576]{0:T(1024)} fusion(...)` as the trace
    prints it -> `fusion.168 f32[1560576]`."""
    m = _OP_TEXT.match(text)
    if not m:
        return text[:80]
    return m.group(1) + (f" {m.group(2)}" if m.group(2) else "")


#: the stat of an `XLA Ops` event's METADATA that holds the operation's name
#: stack, `jit(run_span)/while/body/<scope>/.../<primitive>:` (xprof's
#: "name:type" with an empty type; found on the v5e with jax 0.9.0, PR 27).
#: `jax.profiler.ProfileData` gives an event's own stats and not its
#: metadata's, so `name_stacks` reads the file's protobuf itself.
STACK_STAT = "tf_op"
OPS_LINE = "XLA Ops"


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of every varint and length-delimited field of
    one protobuf message; fixed-width fields are stepped over."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire in (0, 2):
            value, at = _varint(buf, at)
            if wire == 2:
                value, at = buf[at:at + value], at + value
            yield key >> 3, value
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {at}")


def name_stacks(path: str) -> dict:
    """device plane -> [(name stack, start_ns, end_ns), ...] of its
    `XLA Ops` line, on the clock of `read_trace`'s events: every operation
    once, named by the `STACK_STAT` stat of its metadata, or by "" where
    the metadata has none (a `while`, a copy the compiler added). Reads
    `tsl/profiler/protobuf/xplane.proto` by its field numbers: XSpace.planes
    1; XPlane.name 2, .lines 3, .event_metadata 4, .stat_metadata 5 (maps:
    key 1, value 2); XLine.name 2, .timestamp_ns 3, .events 4;
    XEvent.metadata_id 1, .offset_ps 2, .duration_ps 3; XEventMetadata
    .stats 5; XStat.metadata_id 1, .str_value 5, .ref_value 7;
    XStatMetadata.name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for field, value in _fields(plane):
            if field in parts:
                parts[field].append(value)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for entry in parts[5]:
            pair = dict(_fields(entry))
            stat_names[pair.get(1, 0)] = bytes(
                dict(_fields(pair[2])).get(2, b"")).decode()
        stacks = {}
        for entry in parts[4]:
            pair = dict(_fields(entry))
            for field, stat in _fields(pair[2]):
                if field != 5:
                    continue
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) != STACK_STAT:
                    continue
                if 5 in stat:
                    stacks[pair.get(1, 0)] = bytes(stat[5]).decode()
                elif 7 in stat:  # a string kept once, as a stat's name
                    stacks[pair.get(1, 0)] = stat_names.get(stat[7], "")
        for line in parts[3]:
            line_name, line_ns, events = "", 0, []
            for field, value in _fields(line):
                if field == 2:
                    line_name = bytes(value).decode()
                elif field == 3:
                    line_ns = value
                elif field == 4:
                    events.append(value)
            if line_name != OPS_LINE:
                continue
            ops = out.setdefault(name, [])
            for event in events:
                ev = dict(_fields(event))
                start = line_ns + ev.get(2, 0) / 1000.0
                ops.append((stacks.get(ev.get(1, 0), ""), start,
                            start + ev.get(3, 0) / 1000.0))
    return out


def read_trace(path: str) -> dict:
    """An `.xplane.pb` as the event lists `trace_reduce.reduce` takes. Device
    planes are `/device:TPU:<n>`, with the lines `XLA Modules` (one event
    per execution of an executable) and `XLA Ops`; the host plane's thread
    lines hold the benchmark's annotations and the runtime's own events.
    `stacks` are the `XLA Ops` once more, by name stack (`name_stacks`)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    stacks = name_stacks(path)
    devices, spans, activities = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": [],
                     "stacks": stacks.get(plane.name, [])}
            for line in plane.lines:
                key = {"XLA Modules": "modules", OPS_LINE: "ops"}.get(
                    line.name
                )
                if key is None:
                    continue
                for ev in line.events:
                    name = (
                        ev.name.split("(")[0] if key == "modules"
                        else short_op(ev.name)
                    )
                    lines[key].append(
                        (name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
            if lines["ops"] or lines["modules"]:
                devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue  # runtime worker threads: not what Python did
                for ev in line.events:
                    item = (
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns
                    )
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(item)
                    else:
                        activities.append(item)
    window = next(
        ((s, e) for n, s, e in spans if n == WINDOW_SPAN), None
    )
    return {
        "window": window,
        "devices": devices,
        "spans": [ev for ev in spans if ev[0] != WINDOW_SPAN],
        "activities": activities,
    }
