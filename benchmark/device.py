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


def read_trace(path: str) -> dict:
    """An `.xplane.pb` as the event lists `trace_reduce.reduce` takes. Device
    planes are `/device:TPU:<n>`, with the lines `XLA Modules` (one event
    per execution of an executable) and `XLA Ops`; the host plane's thread
    lines hold the benchmark's annotations and the runtime's own events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, activities = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
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
