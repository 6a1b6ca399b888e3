"""local.bwd_ms: the local step's backward, ms per local step: device time
under `local/fwd` inside `transpose(...)` (the gradient), remat's recompute
left out.

From the traced segment's device trace, by the program's named scopes and
host spans (``chipbench.scopes.per_step``), averaged over the cell's
chips.  Nothing to read without a device trace or without scopes."""
from chipbench.scopes import step_reading


def read(ctx):
    return step_reading(ctx, "local.bwd_ms")
