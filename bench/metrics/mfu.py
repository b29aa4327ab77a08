"""Whole-step model FLOP utilization (%): the model FLOPs of every token
processed in the window (prefill rows at their true lengths and decoded
tokens, attention at its true context; ``flops.py``) over window x peak
bf16 FLOP/s."""
import flops


def read(ctx):
    m, rec = ctx["model"], ctx["record"]
    work = sum(flops.chunk_decode_flops(m, c["pos"], c["steps"])
               for c in rec["chunks"])
    work += sum(flops.prefill_flops(m, n) for a in rec["admits"]
                for n in a["lens"])
    if not work:
        return None
    return 100.0 * work / (ctx["trace"].window_s
                           * ctx["peaks"]["bf16_flops_per_s"])
