"""execute_ms.sweep: milliseconds of the program's ``execute`` phase per cell."""
from chipbench.readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "execute", "sweep")
