"""Roofline profiler, per-query resource ledger, digest table, flame export.

PR 2/4 made latency and causality visible (histograms, spans, stitched
traces, the flight recorder); this module makes COST visible and
attributable — the two lenses the SpMM/graph-kernel literature says decide
graph-engine performance (PAPERS.md: arxiv 2011.06391 FusedMM lives or
dies by operational intensity; 2011.08451 finds bottlenecks through DRAM
traffic accounting):

- :class:`ResourceLedger` — a small per-query accumulator carried on the
  ambient context (contextvar, like the span tracer). Every instrumented
  layer accrues into it: cells read/written at the KCVS boundary, index
  hits, host<->device transfer bytes, retry replays, wall by layer. The
  remote-store/index protocols propagate a ledger request flag next to
  the trace header (behind the same feature-bit negotiation, so mixed
  old/new pairs stay byte-compatible) and the serving node echoes its
  measured costs back; the query server echoes the request's ledger to
  the driver in ``status.ledger``.

  Attribution invariant: every PRIMARY accrual also annotates the
  current span with ``ledger.<field>`` attributes; merges of a remote
  peer's echo never re-annotate (the peer's own span already carries the
  fields). A trace's ledger totals therefore equal the sum of the
  ``ledger.*`` attributes over its spans.

- **Roofline cost model** — superstep kernels are lowered once and XLA's
  ``cost_analysis()`` (flops, bytes accessed) harvested from the lowered
  module; a host-side estimator stands in when the backend exposes no
  cost analysis. Operational intensity (flops/byte) and %-of-roofline
  utilization (achieved flops/s over ``min(peak_flops, oi * peak_bw)``)
  land in every OLAP run record, per superstep and per E_cap tier.

- :class:`DigestTable` — traversals normalize to a shape digest (step
  vocabulary + index choice, literals stripped), and a bounded top-K
  table keyed by digest accumulates count / total cost / p50/p95 wall.
  Scrapeable at ``GET /profile`` and via ``janusgraph_tpu top``; slow-op
  and flight-recorder ``slow_span`` events carry the digest so recurring
  offenders group instead of appearing as one-offs.

- **Flamegraph export** — any stitched trace's span tree renders to
  collapsed-stack format (``frame;frame;frame weight_us``) with ledger
  annotations folded into frame names, at ``GET /profile/flame?trace=<id>``
  and ``janusgraph_tpu flame <id>``.

Recording is HOST-ONLY like the rest of the observability layer: no
ledger/digest/cost call may run inside jit-traced code (graphlint JG108,
same family as JG106/JG107).
"""

from __future__ import annotations

import contextvars
import hashlib
import re
import struct
import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

# --------------------------------------------------------------------------
# Resource ledger
# --------------------------------------------------------------------------

#: the counter vocabulary — one shared naming for OLTP profile trees,
#: OLAP run records, span annotations, and the wire blocks
COUNTER_FIELDS = (
    "cells_read",
    "cells_written",
    "bytes_read",
    "bytes_written",
    "index_hits",
    "retries",
    "h2d_bytes",
    "d2h_bytes",
)

#: wire tags (tag-value pairs, so the block can grow without a protocol
#: bump); wall_ns rides the wire but merges into wall_by_layer, not a
#: counter
_FIELD_TAGS: Dict[str, int] = {f: i + 1 for i, f in enumerate(COUNTER_FIELDS)}
_FIELD_TAGS["wall_ns"] = 15
_TAG_FIELDS = {v: k for k, v in _FIELD_TAGS.items()}


class ResourceLedger:
    """Per-query cost accumulator (cells, bytes, hits, retries, walls)."""

    __slots__ = ("counters", "wall_by_layer", "_lock")

    def __init__(self):
        self.counters: Dict[str, int] = {}
        self.wall_by_layer: Dict[str, float] = {}
        self._lock = threading.Lock()

    def add(self, **fields) -> "ResourceLedger":
        with self._lock:
            for k, v in fields.items():
                if v:
                    self.counters[k] = self.counters.get(k, 0) + int(v)
        return self

    def add_wall(self, layer: str, ms: float) -> "ResourceLedger":
        with self._lock:
            self.wall_by_layer[layer] = (
                self.wall_by_layer.get(layer, 0.0) + float(ms)
            )
        return self

    def merge(self, other: "ResourceLedger") -> None:
        with other._lock:
            counters = dict(other.counters)
            walls = dict(other.wall_by_layer)
        self.add(**counters)
        for layer, ms in walls.items():
            self.add_wall(layer, ms)

    def get(self, field: str) -> int:
        with self._lock:
            return self.counters.get(field, 0)

    def op_cells(self) -> int:
        with self._lock:
            return self.counters.get("cells_read", 0) + self.counters.get(
                "cells_written", 0
            )

    def to_dict(self) -> dict:
        with self._lock:
            out: Dict[str, object] = dict(self.counters)
            if self.wall_by_layer:
                out["wall_ms_by_layer"] = {
                    k: round(v, 3) for k, v in self.wall_by_layer.items()
                }
        return out


_LEDGER_VAR: "contextvars.ContextVar[Optional[ResourceLedger]]" = (
    contextvars.ContextVar("janusgraph_tpu_ledger", default=None)
)


def current_ledger() -> Optional[ResourceLedger]:
    return _LEDGER_VAR.get()


@contextmanager
def ledger_scope():
    """Run a block under a fresh ledger; on exit the block's accruals
    merge into the enclosing scope (if any), so a nested ``.profile()``
    still counts toward the surrounding server request."""
    led = ResourceLedger()
    parent = _LEDGER_VAR.get()
    token = _LEDGER_VAR.set(led)
    try:
        yield led
    finally:
        _LEDGER_VAR.reset(token)
        if parent is not None:
            parent.merge(led)


def accrue(**fields) -> None:
    """PRIMARY accrual: add to the ambient ledger AND annotate the current
    span with aggregating ``ledger.<field>`` attributes. No-op outside a
    ledger scope (zero overhead for unprofiled work). Never call from
    jit-traced code (graphlint JG108)."""
    led = _LEDGER_VAR.get()
    if led is None:
        return
    led.add(**fields)
    from janusgraph_tpu.observability import tracer

    sp = tracer.current()
    if sp is not None:
        for k, v in fields.items():
            if v:
                key = f"ledger.{k}"
                sp.attrs[key] = int(sp.attrs.get(key, 0)) + int(v)


def accrue_wall(layer: str, ms: float) -> None:
    """Layer-wall accrual (no span annotation: the span's own duration
    already represents the wall; this just buckets it by layer)."""
    led = _LEDGER_VAR.get()
    if led is not None and ms:
        led.add_wall(layer, ms)


def merge_echo(fields: Optional[dict], layer: str = "") -> None:
    """Merge a remote peer's echoed ledger block into the ambient ledger
    WITHOUT annotating a span — the peer annotated its own span with the
    same fields, and the two sides of the wire must not double-count."""
    if not fields:
        return
    led = _LEDGER_VAR.get()
    if led is None:
        return
    counters = {k: v for k, v in fields.items() if k in _FIELD_TAGS and k != "wall_ns"}
    led.add(**counters)
    wall_ns = fields.get("wall_ns")
    if wall_ns and layer:
        led.add_wall(layer, wall_ns / 1e6)


# ------------------------------------------------------------- wire codec
_LEDGER_VERSION = 1


def encode_ledger_block(fields: dict) -> bytes:
    """``[u8 blen][ver:1][n:1]([tag:1][u64])*`` — length-prefixed like the
    trace-context prefix, so it can ride in front of any response body."""
    pairs = [
        (_FIELD_TAGS[k], int(v))
        for k, v in fields.items()
        if k in _FIELD_TAGS and v
    ]
    payload = bytes([_LEDGER_VERSION, len(pairs)]) + b"".join(
        struct.pack(">BQ", tag, value) for tag, value in pairs
    )
    return bytes([len(payload)]) + payload


def split_ledger_block(body: bytes) -> Tuple[Optional[dict], bytes]:
    """Inverse of :func:`encode_ledger_block`: (fields|None, rest).
    Malformed blocks degrade to None — a bad ledger must never fail the
    response it rides on."""
    if not body:
        return None, body
    blen = body[0]
    if len(body) < 1 + blen or blen < 2:
        return None, body
    payload, rest = body[1 : 1 + blen], body[1 + blen :]
    if payload[0] != _LEDGER_VERSION:
        return None, body
    n = payload[1]
    if len(payload) != 2 + 9 * n:
        return None, body
    fields: Dict[str, int] = {}
    for i in range(n):
        tag, value = struct.unpack_from(">BQ", payload, 2 + 9 * i)
        name = _TAG_FIELDS.get(tag)
        if name is not None:
            fields[name] = value
    return fields, rest


# --------------------------------------------------------------------------
# Query digests
# --------------------------------------------------------------------------

#: literals embedded in step labels (e.g. ``adjacentVertexHasId(1, 2)``)
_LITERAL_RE = re.compile(r"\(.*\)|['\"].*['\"]|\d+", re.S)


def traversal_shape(labels, plan: Optional[dict] = None) -> str:
    """Normalize a traversal to its shape: the step vocabulary joined in
    order with literals stripped, prefixed by the resolved access path
    (index choice included — two queries that differ only in literals or
    in nothing the planner sees share a shape)."""
    plan = plan or {}
    access = plan.get("access", "traversal")
    index = plan.get("index")
    head = f"{access}[{index}]" if index else str(access)
    steps = [_LITERAL_RE.sub("", str(lb)).strip() or "step" for lb in labels]
    return ">".join([head] + steps) if steps else head


def shape_digest(shape: str) -> str:
    """Stable 8-hex-char digest of a shape string."""
    return hashlib.sha1(shape.encode()).hexdigest()[:8]


class DigestTable:
    """Bounded top-K table of query digests ranked by total cost.

    One entry per digest: occurrence count, total wall, total cells, and
    a log-bucket wall histogram for p50/p95. When the table exceeds its
    capacity the entry with the smallest total cost is evicted — heavy
    hitters survive, one-off shapes age out."""

    def __init__(self, capacity: int = 128):
        self.capacity = capacity
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._lock = threading.Lock()

    def configure(self, capacity: Optional[int] = None) -> None:
        with self._lock:
            if capacity is not None and capacity > 0:
                self.capacity = capacity

    def observe(
        self, digest: str, shape: str, wall_ms: float, cells: int = 0
    ) -> None:
        """Record one execution of a digest. Never call from jit-traced
        code (graphlint JG108)."""
        from janusgraph_tpu.observability.metrics_core import Histogram

        with self._lock:
            e = self._entries.get(digest)
            if e is None:
                e = self._entries[digest] = {
                    "digest": digest,
                    "shape": shape,
                    "count": 0,
                    "total_ms": 0.0,
                    "total_cells": 0,
                    "hist": Histogram(),
                }
            e["count"] += 1
            e["total_ms"] += float(wall_ms)
            e["total_cells"] += int(cells)
            e["hist"].observe(float(wall_ms))
            if len(self._entries) > self.capacity:
                victim = min(
                    self._entries, key=lambda d: self._entries[d]["total_ms"]
                )
                del self._entries[victim]

    def mean_cost_ms(self, digest: str) -> Optional[float]:
        """Measured mean wall of one digest (total/count), or None when
        the table has never seen it — the admission controller's price
        lookup (unknown shapes pay its default price instead)."""
        with self._lock:
            e = self._entries.get(digest)
            if e is None or not e["count"]:
                return None
            return e["total_ms"] / e["count"]

    def top(self, k: int = 10) -> List[dict]:
        """The k digests with the largest total cost, descending."""
        with self._lock:
            entries = list(self._entries.values())
        entries.sort(key=lambda e: e["total_ms"], reverse=True)
        out = []
        for e in entries[:k]:
            h = e["hist"]
            out.append({
                "digest": e["digest"],
                "shape": e["shape"],
                "count": e["count"],
                "total_ms": round(e["total_ms"], 3),
                "total_cells": e["total_cells"],
                "p50_ms": round(h.percentile(0.50), 3),
                "p95_ms": round(h.percentile(0.95), 3),
            })
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide digest table; GET /profile and `janusgraph_tpu top` read it
digest_table = DigestTable()


# ------------------------------------------------------------- price book
# Persistence for DigestTable contents (the "price book"): the OLTP shape
# table above and the admission controller's server-side table serialize
# to ONE JSON file next to the autotune record (computer.price-book-path,
# default <computer.checkpoint-path>.pricebook.json), written tmp+rename
# and loaded at graph open / server start — spillover promotion and
# admission pricing warm-start instead of re-learning every process
# lifetime.

_PRICE_BOOK_VERSION = 1


def digest_records(table: DigestTable) -> List[dict]:
    """Serialize a table's entries (histogram bucket counts included, so
    restored p50/p95 match the live table's log-bucket resolution)."""
    with table._lock:
        entries = [dict(e) for e in table._entries.values()]
    out = []
    for e in entries:
        h = e["hist"]
        with h._lock:
            counts = list(h._counts)
            hcount, htotal, hmax = h.count, h.total, h.max
        out.append({
            "digest": e["digest"],
            "shape": e["shape"],
            "count": e["count"],
            "total_ms": e["total_ms"],
            "total_cells": e["total_cells"],
            "hist": {
                "counts": counts, "count": hcount,
                "total": htotal, "max": hmax,
            },
        })
    return out


def restore_digest_records(table: DigestTable, records) -> int:
    """Merge persisted records into a live table (existing entries win —
    fresh in-process measurements outrank a stale file). Malformed
    records are skipped; returns how many were loaded."""
    from janusgraph_tpu.observability.metrics_core import Histogram

    loaded = 0
    for r in records or ():
        try:
            digest = str(r["digest"])
            hist = Histogram()
            hd = r.get("hist") or {}
            counts = list(hd.get("counts") or ())
            if len(counts) == len(hist._counts):
                hist._counts = [int(c) for c in counts]
            hist.count = int(hd.get("count", r["count"]))
            hist.total = float(hd.get("total", r["total_ms"]))
            hist.max = float(hd.get("max", 0.0))
            entry = {
                "digest": digest,
                "shape": str(r.get("shape", "")),
                "count": int(r["count"]),
                "total_ms": float(r["total_ms"]),
                "total_cells": int(r.get("total_cells", 0)),
                "hist": hist,
            }
        except (KeyError, TypeError, ValueError):
            continue
        with table._lock:
            if digest in table._entries:
                continue
            table._entries[digest] = entry
            loaded += 1
            if len(table._entries) > table.capacity:
                victim = min(
                    table._entries,
                    key=lambda d: table._entries[d]["total_ms"],
                )
                del table._entries[victim]
    return loaded


def save_price_book(path: str, tables: Dict[str, DigestTable]) -> None:
    """Atomically persist the named tables (tmp + rename, the autotune
    record's discipline), preserving any OTHER table already in the file.
    Persistence must never fail the caller — I/O errors are swallowed."""
    import json
    import os
    import tempfile

    try:
        payload_tables = dict(load_price_book(path))
        for name, table in tables.items():
            payload_tables[name] = digest_records(table)
        payload = {"version": _PRICE_BOOK_VERSION, "tables": payload_tables}
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".json.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    except OSError:
        return


def load_price_book(path: str) -> Dict[str, List[dict]]:
    """{table name: [records]} from a persisted price book; {} when the
    file is missing, unreadable, or from an unknown version."""
    import json

    try:
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(payload, dict) or payload.get(
        "version"
    ) != _PRICE_BOOK_VERSION:
        return {}
    tables = payload.get("tables")
    return tables if isinstance(tables, dict) else {}


# --------------------------------------------------------------------------
# Roofline cost model
# --------------------------------------------------------------------------

#: device_kind, lower-cased, exactly as ``jax.devices()[0].device_kind``
#: reports it -> (peak flops/s, peak HBM bytes/s, peak MXU flops/s, where
#: the figures come from). The MXU column is the dense-matmul ceiling the
#: dense-feature tier's ``mxu_utilization`` divides by; a TPU's published
#: bf16 peak IS its MXU peak, so the columns coincide. Override via
#: metrics.roofline-peak-flops / -peak-bytes-per-s / -peak-mxu-flops.
#: A kind that is not listed raises in ``device_peaks``: a utilization
#: against another chip's peak is worse than none. The table holds the one
#: accelerator this repo has run on; its key was read off the chip by
#: ``chip_smoke.py`` phase 0 (PR 21).
_DEVICE_PEAKS: Dict[str, Tuple[float, float, float, str]] = {
    "tpu v5 lite": (
        197e12, 819e9, 197e12,
        "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
        "819 GB/s HBM",
    ),
    # platform == "cpu" only (its device_kind is "cpu"): an invented
    # server-class figure that keeps the RELATIVE utilization shape
    # readable in CPU test runs. Not a measurement; goes with ROADMAP D7.
    "cpu": (5e11, 5e10, 1e11, "placeholder, not a measurement"),
}

_ROOFLINE_OVERRIDE = {
    "peak_flops": 0.0, "peak_bytes_per_s": 0.0, "peak_mxu_flops": 0.0,
}


def configure_roofline(
    peak_flops: Optional[float] = None,
    peak_bytes_per_s: Optional[float] = None,
    peak_mxu_flops: Optional[float] = None,
) -> None:
    """Operator override of the device-peak table (0 = auto-detect)."""
    if peak_flops is not None:
        _ROOFLINE_OVERRIDE["peak_flops"] = float(peak_flops)
    if peak_bytes_per_s is not None:
        _ROOFLINE_OVERRIDE["peak_bytes_per_s"] = float(peak_bytes_per_s)
    if peak_mxu_flops is not None:
        _ROOFLINE_OVERRIDE["peak_mxu_flops"] = float(peak_mxu_flops)


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """{peak_flops, peak_bytes_per_s, peak_mxu_flops, device_kind, source}
    for the current (or named) device. Raises ``KeyError`` for a kind the
    table does not list. Host-side metadata only — no device sync."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    row = _DEVICE_PEAKS.get(device_kind.lower())
    if row is None:
        raise KeyError(
            f"device kind {device_kind!r} is not in the roofline peaks "
            f"table (known: {sorted(_DEVICE_PEAKS)}); add its published "
            "peaks to observability/profiler._DEVICE_PEAKS"
        )
    flops, bw, mxu, _origin = row
    source = f"table:{device_kind.lower()}"
    if _ROOFLINE_OVERRIDE["peak_flops"]:
        flops, source = _ROOFLINE_OVERRIDE["peak_flops"], "config"
    if _ROOFLINE_OVERRIDE["peak_bytes_per_s"]:
        bw = _ROOFLINE_OVERRIDE["peak_bytes_per_s"]
        source = "config"
    if _ROOFLINE_OVERRIDE["peak_mxu_flops"]:
        mxu = _ROOFLINE_OVERRIDE["peak_mxu_flops"]
        source = "config"
    return {
        "peak_flops": flops,
        "peak_bytes_per_s": bw,
        "peak_mxu_flops": mxu,
        "device_kind": device_kind,
        "source": source,
    }


def harvest_cost(lowered) -> Optional[dict]:
    """Harvest {flops, bytes_accessed} from a ``jax.stages.Lowered`` (or
    ``Compiled``) via XLA's cost analysis. Returns None when the backend
    exposes nothing usable — callers fall back to the host estimator.
    Host-side only: lowering metadata, never a dispatch."""
    try:
        ca = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-dependent API
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and bytes_accessed <= 0.0:
        return None
    return {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "cost_source": "xla",
    }


def estimate_superstep_cost(
    num_vertices: int,
    num_edges: int,
    msg_cols: int = 1,
    weighted: bool = False,
    arg_bytes: int = 0,
) -> dict:
    """Host-side fallback when XLA cost analysis is unavailable: one BSP
    superstep gathers a message per edge (one multiply when weighted),
    combines at the destination (one op per edge) and applies elementwise
    per vertex. Byte traffic = the shipped argument pytree (or an index +
    message estimate when unknown) plus state in/out."""
    cols = max(1, int(msg_cols))
    flops = float(num_edges) * cols * (2.0 if weighted else 1.0)
    flops += 5.0 * float(num_vertices) * cols
    if arg_bytes <= 0:
        arg_bytes = 8 * num_edges + 4 * num_vertices
    bytes_accessed = float(arg_bytes) + 8.0 * float(num_vertices) * cols
    return {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "cost_source": "estimate",
    }


def roofline_point(
    flops: float, bytes_accessed: float, wall_ms: float, peaks: dict
) -> dict:
    """Operational intensity + utilization for one measured kernel wall.
    Utilization = achieved flops/s over the roofline ceiling at this OI
    (``min(peak_flops, oi * peak_bw)`` — the classic two-segment roof)."""
    oi = flops / bytes_accessed if bytes_accessed > 0 else 0.0
    out = {"operational_intensity": round(oi, 5)}
    if wall_ms and wall_ms > 0 and flops > 0:
        achieved = flops / (wall_ms / 1e3)
        roof = min(peaks["peak_flops"], oi * peaks["peak_bytes_per_s"])
        out["roofline_utilization"] = (
            round(achieved / roof, 6) if roof > 0 else 0.0
        )
    else:
        out["roofline_utilization"] = None
    return out


def attach_roofline(records: List[dict], cost: dict, peaks: dict) -> dict:
    """Stamp per-superstep records with flops / bytes / OI / utilization
    and return the per-E_cap-tier aggregation. ``cost`` is one kernel's
    {flops, bytes_accessed, cost_source} (the same executable serves every
    superstep, so the cost is per dispatch); walls come from each record."""
    tiers: Dict[object, dict] = {}
    for r in records:
        r.setdefault("flops", cost["flops"])
        r.setdefault("bytes_accessed", cost["bytes_accessed"])
        r.setdefault("cost_source", cost["cost_source"])
        point = roofline_point(
            r["flops"], r["bytes_accessed"], r.get("wall_ms", 0.0), peaks
        )
        r.update(point)
        tier = r.get("e_cap", "dense")
        t = tiers.setdefault(
            tier, {"supersteps": 0, "oi_sum": 0.0, "util_sum": 0.0,
                   "util_n": 0},
        )
        t["supersteps"] += 1
        t["oi_sum"] += point["operational_intensity"]
        if point["roofline_utilization"] is not None:
            t["util_sum"] += point["roofline_utilization"]
            t["util_n"] += 1
    out = {}
    for tier, t in tiers.items():
        out[str(tier)] = {
            "supersteps": t["supersteps"],
            "operational_intensity": round(t["oi_sum"] / t["supersteps"], 5),
            "roofline_utilization": (
                round(t["util_sum"] / t["util_n"], 6) if t["util_n"] else None
            ),
        }
    return out


def attach_mxu(records: List[dict], mxu_flops: float, peaks: dict) -> dict:
    """Stamp per-superstep records with the dense tier's MXU accounting:
    ``mxu_flops`` (matmul-attributable flops per superstep — dense layers
    + sddmm dots, from the program's ``matmul_flops``) and
    ``mxu_utilization`` (achieved matmul flops/s over the device's MXU
    peak). Returns the run-level summary block (``run_info["mxu"]``)."""
    peak = float(peaks.get("peak_mxu_flops") or 0.0)
    utils = []
    for r in records:
        r["mxu_flops"] = mxu_flops
        wall = r.get("wall_ms")
        if not mxu_flops:
            r["mxu_utilization"] = 0.0
        elif wall and wall > 0 and peak > 0:
            u = round((mxu_flops / (wall / 1e3)) / peak, 6)
            r["mxu_utilization"] = u
            utils.append(u)
        else:
            r["mxu_utilization"] = None
    return {
        "peak_mxu_flops": peak,
        "per_superstep_flops": mxu_flops,
        "mean_utilization": (
            round(sum(utils) / len(utils), 6) if utils else None
        ),
    }


# --------------------------------------------------------------------------
# Flamegraph export
# --------------------------------------------------------------------------

_FRAME_SANITIZE = re.compile(r"[;\s]+")


def _frame_name(span) -> str:
    """One collapsed-stack frame: the span name, with ledger annotations
    folded in (semicolons and whitespace are the format's separators, so
    they are squeezed out)."""
    name = _FRAME_SANITIZE.sub("_", span.name)
    led = sorted(
        (k[len("ledger."):], v)
        for k, v in span.attrs.items()
        if k.startswith("ledger.")
    )
    if led:
        name += "(" + ",".join(f"{k}:{v}" for k, v in led) + ")"
    return name


def flame_lines(roots) -> List[str]:
    """Render a trace's span trees to collapsed-stack lines
    (``frame;frame;frame weight``, weight = self-time in µs). Roots that
    joined a remote parent (``parent_span_id``) are grafted under that
    span when it is retained locally, so a stitched cross-process trace
    folds into one flame."""
    by_id: Dict[int, List[str]] = {}

    def index(span, prefix: List[str]):
        path = prefix + [_frame_name(span)]
        by_id[span.span_id] = path
        for c in span.children:
            index(c, path)

    attached: List[object] = []
    pending = list(roots)
    # multi-pass graft: a remote-parented root can only be placed once its
    # parent's tree is indexed, whatever order the ring returned them in
    while pending:
        progressed = False
        rest = []
        for r in pending:
            parent_path = by_id.get(r.parent_span_id) if r.parent_span_id else []
            if parent_path is not None:
                index(r, parent_path or [])
                attached.append(r)
                progressed = True
            else:
                rest.append(r)
        if not progressed:
            for r in rest:  # orphaned remote roots: emit as separate stacks
                index(r, [])
                attached.append(r)
            rest = []
        pending = rest

    lines: List[str] = []

    def emit(span, prefix: List[str]):
        path = prefix + [_frame_name(span)]
        child_ms = sum(c.duration_ms for c in span.children)
        self_us = max(0, int(round((span.duration_ms - child_ms) * 1000)))
        lines.append(f"{';'.join(path)} {self_us}")
        for c in span.children:
            emit(c, path)

    for r in attached:
        prefix = by_id[r.span_id][:-1]
        emit(r, prefix)
    return lines


def flame_text(tracer, trace_id) -> str:
    """Collapsed-stack rendering of one retained trace (newline-joined;
    empty string when the trace is not retained)."""
    roots = tracer.find_trace(trace_id)
    return "\n".join(flame_lines(roots))
