"""Least time the chip could take for the outer step's work
(``workcount.outer_step_work``: the larger of its FLOPs over the bf16 peak
and its bytes over the HBM bandwidth), over the step's device time per
call, in percent."""


def read(r):
    calls = r.trace.modules.get("jit_outer_step") if r.trace else None
    if not calls:
        return None
    flops, nbytes = r.counters["outer_work"]
    least = max(flops / r.peaks["bf16_flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(calls) / len(calls))
