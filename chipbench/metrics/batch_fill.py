"""Real rows over bucket rows, over every tier sampler and discriminator
call of the window (%)."""


def read(ctx):
    r = ctx.records
    real = sum(n for _t, _b, n, _s in r.stage_walls) \
        + sum(n for _b, n, _s in r.disc_walls)
    rows = sum(b for _t, b, _n, _s in r.stage_walls) \
        + sum(b for b, _n, _s in r.disc_walls)
    return 100.0 * real / rows if rows else None
