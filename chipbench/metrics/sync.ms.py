"""sync.ms: the averages, ms per local step: device time under
`sync/L<level>` (with its encode, collective and decode parts) and
`sync/fold`.

From the traced segment's device trace, by the program's named scopes and
host spans (``chipbench.scopes.per_step``), averaged over the cell's
chips.  Nothing to read without a device trace or without scopes."""
from chipbench.scopes import step_reading


def read(ctx):
    return step_reading(ctx, "sync.ms")
