"""The pass's one barrier, per pass: ``ProgramStats.sync_s`` of
``TransferProgram.to_device`` (the caller blocked until every in-flight
copy landed).  Moves ``pass_ms``."""


def read(ctx):
    mean = getattr(ctx.run, "counters", {}).get("mean", {})
    if "barrier_s" not in mean:
        return None
    return 1e3 * mean["barrier_s"]
