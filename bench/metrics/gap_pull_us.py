"""Mean time of each chunk gap (as ``chunk_gap_us`` defines the gaps)
under the program's ``fleet.stream.pull`` span, in us: the wait for the
chunk and the pulls of its partial sums to the host."""

from harness import spans


def read(ctx):
    return spans.gap_us(ctx, spans.STREAM_PULL)
