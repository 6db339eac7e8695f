"""Queries completed over the wall seconds of the window."""


def read(ctx):
    w = ctx.window
    return w.result.completed / w.wall_s
