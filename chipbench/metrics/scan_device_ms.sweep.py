"""scan_device_ms.sweep: device milliseconds of the scan programs per cell."""
from chipbench.readers import scan_device_ms


def read(ctx):
    return scan_device_ms(ctx, "sweep")
