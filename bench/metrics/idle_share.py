"""Share of the traced time with at least one request in flight in which no
operation ran on the device, in %."""


def read(ctx):
    return ctx.idle_share()
