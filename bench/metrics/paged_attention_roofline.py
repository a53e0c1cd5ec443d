"""Least time for the paged attention the traced steps needed, over the
kernel's device time, in percent.  The need is counted from each step's
cache lengths (``workcount.paged_attention_bytes`` / ``_flops``: keys and
values of the positions in use, query and output); the kernel is the
Pallas call whose first operand is the (slots, pages) page table, which
is how it appears in the trace until it carries a name of its own."""

KERNEL = r'custom-call\(s32\[\d+,\d+\].*custom_call_target="tpu_custom_call"'


def read(r):
    if r.trace is None:
        return None
    calls, seconds = r.trace.op_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    least = max(r.counters["attn_bytes"] / r.peaks["hbm_bytes_per_s"],
                r.counters["attn_flops"] / r.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
