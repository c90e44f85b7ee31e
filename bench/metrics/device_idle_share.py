"""device_idle_share: 1 - (union of the leaf device ops' intervals / the
traced span), both summed over the run's trace bursts and averaged over the
chips used; None without a device op."""


def read(ctx):
    return ctx["trace"].idle_share()
