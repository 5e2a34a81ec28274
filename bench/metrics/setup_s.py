"""Set-up time: from the start of the process until the load generator
is ready (data made, program built and warmed up, archive loaded or
built), by the host's clock."""


def read(ctx):
    return ctx.setup_s
