"""Bytes ``pack_host`` compared against staging per pass, in MB (1e6 B):
the ``bytes`` the program puts on its ``ArenaEntry.pack_host.compare``
spans (the count it books as ``TransferLedger.compared_bytes``), summed
over the traced window, over the passes.  Moves ``pass_ms``."""


def read(ctx):
    from bench import spans

    value = spans.bytes_per_pass(ctx, spans.COMPARE)
    return None if value is None else value / 1e6
