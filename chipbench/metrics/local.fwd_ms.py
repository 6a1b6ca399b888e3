"""local.fwd_ms: the local step's forward, ms per local step: device time
under the loss's scope `local/fwd`, with what remat recomputes of it in the
backward (`rematted_computation`).

From the traced segment's device trace, by the program's named scopes and
host spans (``chipbench.scopes.per_step``), averaged over the cell's
chips.  Nothing to read without a device trace or without scopes."""
from chipbench.scopes import step_reading


def read(ctx):
    return step_reading(ctx, "local.fwd_ms")
