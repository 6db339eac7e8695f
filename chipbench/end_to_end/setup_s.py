"""Process start to the first timed arrival, compilation included."""


def read(ctx):
    return ctx.setup_s
