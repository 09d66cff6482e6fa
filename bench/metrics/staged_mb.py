"""Bytes ``pack_host`` copied into staging per pass, in MB (1e6 B): the
``bytes`` the program puts on its ``ArenaEntry.pack_host.copy`` spans
(the count it books as ``TransferLedger.staged_bytes``), summed over the
traced window, over the passes; 0 when no bucket changed.  Moves
``pass_ms``."""


def read(ctx):
    from bench import spans

    value = spans.bytes_per_pass(ctx, spans.COPY)
    return None if value is None else value / 1e6
