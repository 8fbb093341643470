"""Kilobytes (1000 B) the program moved between host and device, both
ways, per prefill-chunk or decode token scheduled, over the window's ticks
(`TickRecord.h2d_bytes` + `.d2h_bytes` over `.chunk_tokens` +
`.decode_tokens`); nothing where the ticks carry no byte counters."""


def read(run):
    if not run.ticks or not hasattr(run.ticks[0], "h2d_bytes"):
        return None
    tokens = sum(x.chunk_tokens + x.decode_tokens for x in run.ticks)
    moved = sum(x.h2d_bytes + x.d2h_bytes for x in run.ticks)
    return moved / tokens / 1e3 if tokens else None
