"""JG106: a phase inside jit-traced code. A phase is a recording call
like a span: in a traced body it runs at TRACE time, so its timer gets
one self time per compile (the tracing's, not the execution's) and its
profiler annotation covers no device work at all."""

import jax

from janusgraph_tpu.observability import tracer


@jax.jit
def superstep(state):
    with tracer.phase("executor.dispatch"):  # expect: JG106
        return state * 2.0


def body(state):
    with tracer.phase("spill.lock_wait", wait=True):  # expect: JG106
        out = state + 1.0
    return out


fn = jax.jit(body)
