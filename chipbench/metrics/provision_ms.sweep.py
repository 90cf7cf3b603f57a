"""provision_ms.sweep: milliseconds of the program's ``provision`` phase per cell."""
from chipbench.readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "provision", "sweep")
