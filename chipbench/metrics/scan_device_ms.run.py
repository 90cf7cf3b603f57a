"""scan_device_ms.run: device milliseconds of the scan programs per policy-week."""
from chipbench.readers import scan_device_ms


def read(ctx):
    return scan_device_ms(ctx, "run")
