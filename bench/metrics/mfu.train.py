"""Model FLOP/s utilization of the traced rounds: forward and backward
FLOPs per token (``workcount.train_flops_per_token``) times tokens per
second over the traced rounds' wall time, over the chips' bf16 peak."""


def read(r):
    rate = r.counters.get("tokens_per_s")
    if not rate:
        return None
    return (100.0 * rate * r.counters["flops_per_token"]
            / (r.chips * r.peaks["bf16_flops_per_s"]))
