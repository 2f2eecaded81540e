"""Time per entry call at the edges of the chunk loop, in ms: the
program's ``fleet.stream.setup`` (flatten, pad, predictor state,
sharding) and ``fleet.stream.finish`` (final-state pulls, summary) spans
of every stream call, summed per traced call."""

from harness import spans


def read(ctx):
    return spans.per_call_ms(ctx, spans.STREAM_SETUP, spans.STREAM_FINISH)
