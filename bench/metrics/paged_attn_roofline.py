"""Paged decode attention's share of its roofline (%): the least time the
chip needs for the kernel's work in the window - per active slot and step,
the larger of its FLOPs over peak compute and its needed bytes over peak
bandwidth (``flops.paged_attn_cost``: live positions only, never the dead
blocks a kernel may walk) - over the kernel's summed device time."""
import flops
import xplane


def read(ctx):
    kernel_s = xplane.op_s(ctx["trace"], "paged_attention")
    if not kernel_s:
        return None
    m, pk = ctx["model"], ctx["peaks"]
    least = 0.0
    for c in ctx["record"]["chunks"]:
        for p in c["pos"]:
            for s in range(c["steps"]):
                f, b = flops.paged_attn_cost(m, p + s)
                least += max(f / pk["bf16_flops_per_s"],
                             b / pk["hbm_bytes_per_s"])
    if not least:
        return None
    return 100.0 * least / kernel_s
