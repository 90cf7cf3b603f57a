"""unprofiled_ms.run: milliseconds per policy-week of the window outside every
program phase (scan packing and table building, result rows)."""
from chipbench.readers import unprofiled_ms


def read(ctx):
    return unprofiled_ms(ctx, "run")
