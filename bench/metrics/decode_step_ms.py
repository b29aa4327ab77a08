"""Device milliseconds per decode step: the fused decode-chunk programs'
device time in the window over the decode steps they ran."""
import xplane


def read(ctx):
    dev = xplane.module_s(ctx["trace"], "decode_chunk")
    steps = sum(c["steps"] for c in ctx["record"]["chunks"])
    if not dev or not steps:
        return None
    return 1e3 * dev / steps
