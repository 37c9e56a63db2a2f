"""Median over the window's streamed chunks of the time from the end of a
chunk's ``stream.emit`` on the decode thread to the end of its
``http.sse_write`` on the handler thread (same request, same sequence
number), in ms: the queue hop, the thread switch and the SSE write."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    if spans is None:
        return None
    return ps.median_ms(ps.sse_lags(ps.answered(ctx, spans)))
