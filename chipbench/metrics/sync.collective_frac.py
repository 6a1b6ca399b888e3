"""sync.collective_frac: the share of the device's busy time that its
collective operations (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute) take, from the traced segment's device trace: per chip
its collectives' seconds over its busy seconds, then the mean over the
cell's chips.  Nothing to read without a device trace."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    shares = [t.collective_s.get(dev, 0.0) / busy
              for dev, busy in t.busy_s_per_device.items() if busy > 0]
    return sum(shares) / len(shares) if shares else None
