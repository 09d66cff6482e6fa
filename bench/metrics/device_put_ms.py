"""The ``device_put`` enqueue of a pass's buckets alone, per pass:
``TransferLedger.enqueue_s`` summed over the program's regions.  With
``enqueue_ms`` it splits the host side of the engine into packing and
enqueueing.  Moves ``pass_ms``."""


def read(ctx):
    mean = getattr(ctx.run, "counters", {}).get("mean", {})
    if "device_put_s" not in mean:
        return None
    return 1e3 * mean["device_put_s"]
