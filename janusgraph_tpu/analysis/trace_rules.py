"""JG1xx trace-safety rules for the OLAP/parallel compiled paths.

JG101  Python coercion (`float()/int()/bool()`) of, or `if`/`while`/`assert`
       branching on, a traced value inside a jit context. Coercion forces a
       device->host sync per call; branching raises
       TracerBoolConversionError at trace time or, worse, bakes one branch
       into the executable.
JG102  numpy call inside a jit/pmap/shard_map body: numpy pulls the traced
       value to host (ConcretizationTypeError) or silently constant-folds.
JG103  retrace hazards: `static_argnums`/`static_argnames`/`donate_argnums`
       given a non-constant expression (per-call variation = one executable
       per call), and jit-like wrapping inside a loop body (a fresh
       callable each iteration defeats the compile cache).
JG104  donated buffer reuse: an argument passed at a donate_argnums
       position is dead after the call — its HBM was handed to the output.
JG105  host sync in a jit context: `.item()`, `.tolist()`,
       `.block_until_ready()`, `jax.device_get` on traced values.
JG106  telemetry recording inside a jit context: a metric/span call on
       the observability registry/tracer (`metrics.counter(...).inc()`,
       `with span("...")`, `tracer.phase("...")`, `registry.time(...)`,
       ...) in a traced body runs at TRACE time — it records once per
       compile, not per execution, and any traced attribute value is a
       host-sync hazard.
       Record from host code after the dispatch (see
       TPUExecutor._finish_run for the sanctioned pattern).
JG107  structured-log / flight-recorder call inside a jit context:
       `flight_recorder.record(...)`, `recorder.dump(...)`, or a
       `logger.info/warning/error(...)` emitted from a traced body fires
       once per COMPILE with trace-time values (and coercing a traced
       field is a hidden sync). Same fix as JG106: emit from host code
       after the dispatch.
JG108  profiler / resource-ledger / cost-model call inside a jit context:
       `accrue(...)`, `ledger.add(...)`, `digest_table.observe(...)`,
       `harvest_cost(...)` / `estimate_superstep_cost(...)` from a traced
       body accrues once per COMPILE with trace-time values (and cost
       harvesting re-enters tracing). Same family as JG106/JG107: accrue
       and harvest from host code after the dispatch (see
       TPUExecutor._superstep_cost / _finish_run for the sanctioned
       pattern).
"""

from __future__ import annotations

import ast
from typing import List, Set

from janusgraph_tpu.analysis.core import Finding, RULES
from janusgraph_tpu.analysis.tracing import (
    TaintWalker,
    find_traced_defs,
    terminal_name,
)

_JIT_ENTRY_NAMES = {"jit", "pjit", "pmap"}  # wrappers that take argnums kws


def _finding(rule: str, mod, node: ast.AST, message: str) -> Finding:
    return Finding(
        rule, RULES[rule].severity, mod.path,
        getattr(node, "lineno", 1), getattr(node, "col_offset", 0), message,
    )


def _is_constant_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return all(_is_constant_expr(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp):
        return _is_constant_expr(node.operand)
    return False


def _check_traced_bodies(mod, traced) -> List[Finding]:
    out: List[Finding] = []
    for td in traced.values():
        if isinstance(td.node, ast.Lambda):
            continue
        walker = TaintWalker(td, mod)
        walker.run()
        name = getattr(td.node, "name", "<lambda>")
        for kind, node, detail in walker.events:
            if kind == "coerce":
                out.append(_finding(
                    "JG101", mod, node,
                    f"`{detail}()` applied to a traced value in jit "
                    f"context `{name}` — forces a host sync (or fails "
                    f"under jit); keep it on device or hoist to host code",
                ))
            elif kind == "branch":
                out.append(_finding(
                    "JG101", mod, node,
                    f"branch on a traced value in jit context `{name}` — "
                    f"use jnp.where / lax.cond instead of Python control "
                    f"flow",
                ))
            elif kind == "hostsync":
                out.append(_finding(
                    "JG105", mod, node,
                    f"`{detail}` on a traced value in jit context "
                    f"`{name}` — host sync inside a compiled body",
                ))
        # numpy calls anywhere in the traced body (taint-independent: numpy
        # output is a host constant even when the inputs are static)
        for sub in ast.walk(td.node):
            if not isinstance(sub, ast.Call):
                continue
            root = sub.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in mod.numpy_names:
                out.append(_finding(
                    "JG102", mod, sub,
                    f"numpy call `{ast.unparse(sub.func)}` inside jit "
                    f"context `{name}` — use jnp (numpy breaks tracing "
                    f"or constant-folds host-side)",
                ))
    return out


def _check_jit_callsites(mod) -> List[Finding]:
    """JG103: non-constant argnums + jit-in-loop."""
    out: List[Finding] = []

    class V(ast.NodeVisitor):
        def __init__(self):
            self.loop_depth = 0

        def _loop(self, node):
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_For = _loop
        visit_While = _loop

        def visit_FunctionDef(self, node):
            # a def inside a loop resets loop context for its body
            saved, self.loop_depth = self.loop_depth, 0
            self.generic_visit(node)
            self.loop_depth = saved

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            t = terminal_name(node.func)
            if t in _JIT_ENTRY_NAMES:
                for kw in node.keywords:
                    if kw.arg in (
                        "static_argnums", "static_argnames", "donate_argnums"
                    ) and not _is_constant_expr(kw.value):
                        out.append(_finding(
                            "JG103", mod, node,
                            f"`{kw.arg}` is not a constant literal — a "
                            f"per-call value retraces on every invocation",
                        ))
                if self.loop_depth > 0:
                    out.append(_finding(
                        "JG103", mod, node,
                        f"`{ast.unparse(node.func)}` called inside a loop "
                        f"body — each iteration builds a fresh executable "
                        f"(retrace); hoist and cache the jitted callable",
                    ))
            self.generic_visit(node)

    V().visit(mod.tree)
    return out


#: receiver names that identify the telemetry layer (the observability
#: singletons and their conventional aliases)
_TELEMETRY_ROOTS = {"metrics", "registry", "tracer", "telemetry"}
#: method names that record into that layer
_TELEMETRY_RECORDERS = {
    "counter", "timer", "histogram", "gauge", "time", "span", "phase",
    "record_span", "record_run", "inc", "update", "observe", "set_gauge",
    "annotate",
}
#: bare-name calls from `from janusgraph_tpu.observability import span`
_SPAN_BARE_NAMES = {"span", "record_span"}


def _chain_names(node: ast.AST) -> Set[str]:
    """Every Name id and Attribute attr along a call/attribute chain:
    `metrics.counter("x").inc` -> {"metrics", "counter", "inc"}."""
    out: Set[str] = set()
    while node is not None:
        if isinstance(node, ast.Attribute):
            out.add(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Name):
            out.add(node.id)
            return out
        else:
            return out
    return out


def _check_telemetry_in_trace(mod, traced) -> List[Finding]:
    """JG106: metric/span recording calls inside traced bodies. The
    receiver chain must touch a telemetry root name — `.update()` on a
    dict or `x.at[i].set(v)` never match."""
    out: List[Finding] = []
    for td in traced.values():
        name = getattr(td.node, "name", "<lambda>")
        for sub in ast.walk(td.node):
            if not isinstance(sub, ast.Call):
                continue
            t = terminal_name(sub.func)
            hit = isinstance(sub.func, ast.Name) and t in _SPAN_BARE_NAMES
            if (
                not hit
                and isinstance(sub.func, ast.Attribute)
                and t in _TELEMETRY_RECORDERS
            ):
                hit = bool(_chain_names(sub.func.value) & _TELEMETRY_ROOTS)
            if hit:
                out.append(_finding(
                    "JG106", mod, sub,
                    f"telemetry call `{ast.unparse(sub.func)}` inside jit "
                    f"context `{name}` — it records once per compile (not "
                    f"per execution) and traced attribute values force a "
                    f"host sync; record host-side after the dispatch",
                ))
    return out


#: receiver names identifying the flight recorder / structured-log layer
_FLIGHT_ROOTS = {"flight", "recorder", "flight_recorder"}
_FLIGHT_RECORDERS = {"record", "dump"}
#: structured-logger receivers (observability.logging.get_logger naming
#: conventions) and their emit methods
_LOGGER_ROOTS = {"logger", "log", "slog", "structured_logger"}
_LOGGER_EMITTERS = {"debug", "info", "warning", "error", "exception",
                    "critical"}


def _check_flight_in_trace(mod, traced) -> List[Finding]:
    """JG107: flight-recorder records / structured-log emits inside traced
    bodies. Receiver-chain matched like JG106, so `math.log(x)` or a
    dict's `.update()` never hit."""
    out: List[Finding] = []
    for td in traced.values():
        name = getattr(td.node, "name", "<lambda>")
        for sub in ast.walk(td.node):
            if not isinstance(sub, ast.Call) or not isinstance(
                sub.func, ast.Attribute
            ):
                continue
            t = terminal_name(sub.func)
            chain = _chain_names(sub.func.value)
            hit = (
                (t in _FLIGHT_RECORDERS and chain & _FLIGHT_ROOTS)
                or (t in _LOGGER_EMITTERS and chain & _LOGGER_ROOTS)
            )
            if hit:
                out.append(_finding(
                    "JG107", mod, sub,
                    f"flight/log call `{ast.unparse(sub.func)}` inside jit "
                    f"context `{name}` — it fires once per compile with "
                    f"trace-time values; emit host-side after the dispatch",
                ))
    return out


#: receiver names identifying the profiler / resource-ledger layer
#: (observability/profiler.py singletons and conventional aliases)
_PROFILER_ROOTS = {"profiler", "ledger", "digest_table", "resource_ledger"}
#: recording/harvest methods on those receivers
_PROFILER_RECORDERS = {
    "accrue", "accrue_wall", "add", "add_wall", "merge", "merge_echo",
    "observe", "harvest_cost", "estimate_superstep_cost",
    "attach_roofline",
}
#: bare-name calls from `from ...profiler import accrue` etc.
_PROFILER_BARE_NAMES = {
    "accrue", "accrue_wall", "ledger_scope", "current_ledger",
    "merge_echo", "harvest_cost", "estimate_superstep_cost",
    "attach_roofline",
}


def _check_profiler_in_trace(mod, traced) -> List[Finding]:
    """JG108: ledger/digest/cost-model calls inside traced bodies.
    Receiver-chain matched like JG106 — a set's `.add()` or a dict's
    `.merge()` never hit unless the chain touches a profiler root."""
    out: List[Finding] = []
    for td in traced.values():
        name = getattr(td.node, "name", "<lambda>")
        for sub in ast.walk(td.node):
            if not isinstance(sub, ast.Call):
                continue
            t = terminal_name(sub.func)
            hit = (
                isinstance(sub.func, ast.Name)
                and t in _PROFILER_BARE_NAMES
            )
            if (
                not hit
                and isinstance(sub.func, ast.Attribute)
                and t in _PROFILER_RECORDERS
            ):
                hit = bool(_chain_names(sub.func.value) & _PROFILER_ROOTS)
            if hit:
                out.append(_finding(
                    "JG108", mod, sub,
                    f"profiler/ledger call `{ast.unparse(sub.func)}` "
                    f"inside jit context `{name}` — it accrues once per "
                    f"compile with trace-time values (and cost harvesting "
                    f"re-enters tracing); accrue host-side after the "
                    f"dispatch",
                ))
    return out


def _check_donated_reuse(mod) -> List[Finding]:
    """JG104: best-effort, function-scope-local. Tracks
    `f = jax.jit(g, donate_argnums=(i,))` then `f(x, ...)` then a later
    read of `x`."""
    out: List[Finding] = []

    def donated_positions(call: ast.Call) -> Set[int]:
        pos: Set[int] = set()
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, int):
                        pos.add(n.value)
        return pos

    def scan_scope(body: List[ast.stmt]):
        jitted: dict = {}  # fn name -> donated positions
        dead: dict = {}  # var name -> line donated at
        for stmt in body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, ast.Load
                ) and sub.id in dead:
                    out.append(_finding(
                        "JG104", mod, sub,
                        f"`{sub.id}` was donated to a jit call on line "
                        f"{dead[sub.id]} — its buffer no longer holds the "
                        f"value (donated HBM is reused for the output)",
                    ))
                    del dead[sub.id]  # one report per variable
            if isinstance(stmt, ast.Assign) and isinstance(
                stmt.value, ast.Call
            ):
                call = stmt.value
                if terminal_name(call.func) in _JIT_ENTRY_NAMES:
                    pos = donated_positions(call)
                    if pos:
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                jitted[t.id] = pos
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                fname = sub.func.id if isinstance(sub.func, ast.Name) else None
                if fname in jitted:
                    for i in jitted[fname]:
                        if i < len(sub.args) and isinstance(
                            sub.args[i], ast.Name
                        ):
                            dead[sub.args[i].id] = sub.lineno
            if isinstance(stmt, ast.Assign):
                # rebinding AFTER the call registration: `x = step(x, ...)`
                # rebinds x to the jit OUTPUT, which is a live buffer
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        dead.pop(t.id, None)

    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_scope(node.body)
    scan_scope(mod.tree.body)
    return out


def check_module(mod, traced=None) -> List[Finding]:
    """`traced` is the precomputed traced-def map for this module — with
    graphlint v2 the driver computes it ONCE per module via the
    whole-program call graph (callgraph.propagate_traced), so cross-module
    jit-taint chains reach here; standalone callers omit it and get the
    module-local view."""
    if traced is None:
        traced = find_traced_defs(mod)
    out = _check_traced_bodies(mod, traced)
    out.extend(_check_jit_callsites(mod))
    out.extend(_check_donated_reuse(mod))
    out.extend(_check_telemetry_in_trace(mod, traced))
    out.extend(_check_flight_in_trace(mod, traced))
    out.extend(_check_profiler_in_trace(mod, traced))
    return out
