"""step.mfu: model FLOPs utilization of the training step while the device
runs it, in %: FLOPs per token (``chipbench.flops``) times the tokens of
the traced calls, over the device's busy seconds in the trace (summed over
the cell's chips) times the chip's peak bf16 FLOP/s (``chipbench.peaks``).
Host gaps are ``device.idle_frac``'s; this is the round programs' own
rate.  Nothing to read where the run recorded no device trace (a host-only
backend)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0 or not ctx.traced_tokens:
        return None
    peak = ctx.peaks.peak(ctx.device_kind)["bf16_flops_per_s"]
    return (100.0 * ctx.flops_per_token * ctx.traced_tokens
            / (t.busy_s * ctx.chips * peak))
