"""Host time per engine step outside the model step: the benchmark's span
around ``ServeEngine.step`` less the engine's own ``device_step`` spans
inside it (admission, planning, page bookkeeping, commit), mean over the
traced steps."""


def read(r):
    steps = r.spans.durations("bench.step")
    dev = r.spans.within("device_step", "bench.step")
    if not steps or not any(dev):
        return None
    return 1e3 * sum(s - sum(d) for s, d in zip(steps, dev)) / len(steps)
