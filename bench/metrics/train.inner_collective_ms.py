"""Device time of the collective ops (``harness/train_rounds_mesh``'s
``COLLECTIVE``: all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute and their async forms) per call of the jitted inner
step, ``jit_train_step``, mean over chips: the tensor-parallel traffic
inside a cluster that the step does not hide."""


def read(r):
    return (r.counters.get("collective_ms") or {}).get("jit_train_step")
