"""Mean time of each chunk gap (as ``chunk_gap_us`` defines the gaps)
under the program's ``fleet.stream.dispatch`` span, in us: the call of
the chunk program, its argument handling and enqueue."""

from harness import spans


def read(ctx):
    return spans.gap_us(ctx, spans.STREAM_DISPATCH)
