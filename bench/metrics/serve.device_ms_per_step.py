"""The engine's ``device_step`` span: the jitted paged decode step from
its call to the host's fetch of the next tokens, mean over the traced
steps (the spans inside the benchmark's ``bench.step`` spans)."""


def read(r):
    dev = [d for ds in r.spans.within("device_step", "bench.step")
           for d in ds]
    return 1e3 * sum(dev) / len(dev) if dev else None
