"""Prefill's FLOP utilization (%): the FLOPs of the true prompt tokens
admitted in the window over the device time of the prefill and insert
programs there, over peak bf16 FLOP/s."""
import flops
import xplane


def read(ctx):
    dev = xplane.module_s(ctx["trace"], "prefill")
    if not dev:
        return None
    dev += xplane.module_s(ctx["trace"], "insert") or 0.0
    work = sum(flops.prefill_flops(ctx["model"], n)
               for a in ctx["record"]["admits"] for n in a["lens"])
    if not work:
        return None
    return 100.0 * work / (dev * ctx["peaks"]["bf16_flops_per_s"])
