"""device_idle_share: 1 - (union of the leaf device ops' intervals / the
traced span), both summed over the trace bursts that began inside a window
sweep and averaged over the chips used; None without a device op there.
Bursts that began between sweeps (`bench.population`) measure the host's
work, not the device's, and are left out."""


def read(ctx):
    return ctx["trace"].in_sweeps().idle_share()
