"""Device milliseconds per decode step that the decode-chunk programs spend
in the substrate's emulation around its matmuls: ops whose innermost named
scope is ``imc`` (operand statistics and quantization, the noise and ADC
epilogue; ``core/imc_linear.linear``).  Nothing to read from a program
without scopes."""
import scopes


def read(ctx):
    found = scopes.analyze(ctx)
    steps = scopes.decode_steps(ctx)
    if not found["scoped"] or not steps:
        return None
    return 1e3 * found["decode"].get("imc", 0.0) / steps
