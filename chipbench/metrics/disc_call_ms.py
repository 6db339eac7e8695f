"""Mean host milliseconds of a discriminator call at the top bucket, to
the confidences on the host."""


def read(ctx):
    top = max(ctx.config["batch_buckets"])
    walls = [s for b, _n, s in ctx.records.disc_walls if b == top]
    return 1e3 * sum(walls) / len(walls) if walls else None
