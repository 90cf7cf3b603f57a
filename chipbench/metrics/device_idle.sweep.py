"""device_idle.sweep: share of the traced request with no program on the device."""
from chipbench.readers import device_idle


def read(ctx):
    return device_idle(ctx, "sweep")
