"""The arena unpack program's share of its roofline, in %: the least time
of the bytes every pass's unpack must read and write (each params and
cache leaf once out of its bucket and once as a leaf, ``costs.unpack_bytes``)
at the chip's HBM bandwidth, over the device time of the unpack program's
events (``jit__unpack``) in the traced window.  Moves ``pass_ms``."""

PROGRAM = "jit__unpack"


def read(ctx):
    from bench.costs import unpack_bytes

    counters = getattr(ctx.run, "counters", {})
    busy = ctx.summary.device_s([PROGRAM]) if ctx.summary else 0.0
    if not busy or "passes" not in counters:
        return None
    tr = ctx.traffic
    least = (counters["passes"]
             * unpack_bytes(ctx.family, ctx.cfg, tr["slots"], tr["max_seq"])
             / ctx.peaks()["hbm_bytes_per_s"])
    return 100.0 * least / busy
