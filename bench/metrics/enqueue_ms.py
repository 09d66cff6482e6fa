"""Host time of a pass before its barrier, per pass: ``pack_host`` (memcmp
fingerprint and memcpy into staging) plus the ``device_put`` enqueue of
every bucket.  Read as the benchmark's host clock around
``TransferProgram.to_device`` less the program's own ``ProgramStats``
barrier (``sync_s``) and ``finish_s``.  Moves ``pass_ms``."""


def read(ctx):
    mean = getattr(ctx.run, "counters", {}).get("mean", {})
    if "host_before_barrier_s" not in mean:
        return None
    return 1e3 * mean["host_before_barrier_s"]
