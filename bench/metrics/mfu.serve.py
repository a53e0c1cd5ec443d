"""Model FLOP/s utilization of the traced engine steps: the FLOPs of every
token fed (prompt or generated; ``workcount.decode_flops`` at its cache
length) over the steps' wall time, over the chips' bf16 peak."""


def read(r):
    wall = r.counters.get("window_s")
    if not wall or not r.counters.get("flops"):
        return None
    return (100.0 * r.counters["flops"]
            / (wall * r.chips * r.peaks["bf16_flops_per_s"]))
