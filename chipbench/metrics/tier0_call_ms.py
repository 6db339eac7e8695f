"""Mean host milliseconds of a tier-0 sampler call at the top bucket,
to ``block_until_ready``."""


def read(ctx):
    return ctx.mean_stage_ms(0, max(ctx.config["batch_buckets"]))
