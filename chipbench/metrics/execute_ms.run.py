"""execute_ms.run: milliseconds of the program's ``execute`` phase per policy-week."""
from chipbench.readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "execute", "run")
