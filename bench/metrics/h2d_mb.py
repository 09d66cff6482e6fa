"""Bytes shipped host to device per pass, in MB (1e6 B): the program's
merged ``TransferLedger.h2d_bytes``, a count.  Moves ``pass_ms``."""


def read(ctx):
    mean = getattr(ctx.run, "counters", {}).get("mean", {})
    if "h2d_bytes" not in mean:
        return None
    return mean["h2d_bytes"] / 1e6
