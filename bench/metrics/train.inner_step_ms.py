"""Device time per call of the jitted inner step, ``jit_train_step`` in
the trace (``launch/steps.make_train_step``)."""


def read(r):
    calls = r.trace.modules.get("jit_train_step") if r.trace else None
    return 1e3 * sum(calls) / len(calls) if calls else None
