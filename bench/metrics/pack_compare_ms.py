"""``pack_host``'s memcmp against staging, per pass, in ms: the seconds of
the program's ``ArenaEntry.pack_host.compare`` spans in the traced window
(``np.asarray`` and the byte compare of every leaf not skipped by
identity, one span per marshal region) over the passes.  Moves
``pass_ms``."""


def read(ctx):
    from bench import spans

    value = spans.seconds_per_pass(ctx, spans.COMPARE)
    return None if value is None else 1e3 * value
