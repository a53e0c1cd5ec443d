"""Device time per call of the jitted outer step, ``jit_outer_step`` in
the trace (``launch/steps.make_outer_step``)."""


def read(r):
    calls = r.trace.modules.get("jit_outer_step") if r.trace else None
    return 1e3 * sum(calls) / len(calls) if calls else None
