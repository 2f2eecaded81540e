"""Mean time of each chunk gap (as ``chunk_gap_us`` defines the gaps)
under the program's ``fleet.stream.stage`` span, in us: the next chunk's
host slice and copy, transfer to the device and sharding."""

from harness import spans


def read(ctx):
    return spans.gap_us(ctx, spans.STREAM_STAGE)
