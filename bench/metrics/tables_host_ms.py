"""Time per entry call under the program's ``fleet.tables`` span, in ms:
``controller.fleet_bin_tables`` whole (sweep rows, the grid-sweep
program and its dispatch, the closed-form tables), summed per traced call."""

from harness import spans


def read(ctx):
    return spans.per_call_ms(ctx, spans.FLEET_TABLES)
