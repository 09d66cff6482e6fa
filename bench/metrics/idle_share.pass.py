"""The device's idle share of the traced window of a transfer cell, in %:
1 - (union of device op intervals) / window.  Moves ``pass_ms``."""


def read(ctx):
    if ctx.summary is None or not ctx.summary.devices:
        return None
    return 100.0 * ctx.summary.idle_share
