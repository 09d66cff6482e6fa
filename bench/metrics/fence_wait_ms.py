"""``pack_host``'s waits on staging fences, per pass, in ms: the seconds
of the program's ``ArenaEntry.pack_host.fence_wait`` spans in the traced
window over the passes; 0 when no fence had to be waited.  Moves
``pass_ms``."""


def read(ctx):
    from bench import spans

    value = spans.seconds_per_pass(ctx, spans.FENCE_WAIT)
    return None if value is None else 1e3 * value
