"""local.other_ms: the rest of the local block, ms per local step: device
time under `local` outside the loss and the update (the scan's bookkeeping,
and what XLA's loop passes rebuild under the loop's name, such as bfloat16
casts of float32 weights).

From the traced segment's device trace, by the program's named scopes and
host spans (``chipbench.scopes.per_step``), averaged over the cell's
chips.  Nothing to read without a device trace or without scopes."""
from chipbench.scopes import step_reading


def read(ctx):
    return step_reading(ctx, "local.other_ms")
