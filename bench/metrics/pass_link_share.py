"""The whole pass's share of the host-to-device link, in %: bytes shipped
per pass over (pass time x the link's bandwidth).  The bandwidth is
measured in the traced run's set-up by one plain ``device_put`` of a
buffer the size of the largest bucket (the faster of two).  Moves
``pass_ms``."""


def read(ctx):
    counters = getattr(ctx.run, "counters", {})
    if not counters.get("link_bytes_per_s") or not counters.get("passes"):
        return None
    pass_s = counters["window_s"] / counters["passes"]
    return 100.0 * counters["mean"]["h2d_bytes"] / (pass_s * counters["link_bytes_per_s"])
