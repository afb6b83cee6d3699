"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window (``bench/trace.py``)."""


def read(ctx):
    if ctx.plane is None or not ctx.device.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
