"""Device time of the collective ops between clusters
(``harness/train_rounds_mesh``'s ``COLLECTIVE`` whose devices ``crosses``
the clusters) per call of the jitted outer step, ``jit_outer_step``, mean
over chips: the exchange of the outer step's pseudo-gradients."""


def read(r):
    return (r.counters.get("cross_cluster_ms") or {}).get("jit_outer_step")
