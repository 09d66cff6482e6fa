"""``pack_host``'s memcpy into staging, per pass, in ms: the seconds of
the program's ``ArenaEntry.pack_host.copy`` spans in the traced window
(one per bucket that changed, copying its stale slots into the spare
buffer) over the passes; 0 when no bucket changed.  Moves ``pass_ms``."""


def read(ctx):
    from bench import spans

    value = spans.seconds_per_pass(ctx, spans.COPY)
    return None if value is None else 1e3 * value
