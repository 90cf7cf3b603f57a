"""decide_ms.run: milliseconds of the program's ``decide`` phase per policy-week."""
from chipbench.readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "decide", "run")
