"""Host time per entry call spent building the call's inputs, in ms:
the program's ``entry.inputs`` spans (suite build and stacking,
availability fractions and scales, per-candidate tables and planes),
summed per traced call."""

from harness import spans


def read(ctx):
    return spans.per_call_ms(ctx, spans.ENTRY_INPUTS)
