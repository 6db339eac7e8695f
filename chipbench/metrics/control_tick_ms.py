"""Mean host milliseconds of ``ControlPlane.tick`` over the window."""


def read(ctx):
    walls = ctx.records.tick_walls
    return 1e3 * sum(walls) / len(walls) if walls else None
