"""plan.host_gap_ms: device idle ms per global-round call while the host is
inside `hsgd.run_rounds`: each idle gap goes whole to the innermost `hsgd.*`
span holding most of it.

From the traced segment's device trace, by the program's named scopes and
host spans (``chipbench.scopes.per_step``), averaged over the cell's
chips.  Nothing to read without a device trace or without scopes."""
from chipbench.scopes import step_reading


def read(ctx):
    return step_reading(ctx, "plan.host_gap_ms")
