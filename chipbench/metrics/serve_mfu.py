"""Useful UNet and discriminator operations of the sampler and
discriminator calls wholly inside the traced stretch (their real rows,
counted from shapes), over the stretch's length and the chip's peak (%).
The traced stretch, not the window's wall, is the time base: stopping
the profiler inside the window adds seconds to the traced run's wall."""
from chipbench import flops


def read(ctx):
    calls = ctx.traced_stage_calls()
    if ctx.peak is None or not calls:
        return None
    prompt_len = ctx.config["prompt_len"]
    unet = [t.model.unet_flops(t.m, 1, prompt_len) for t in ctx.models]
    disc = flops.discriminator_flops(ctx.config["discriminator"], 1,
                                     ctx.image_size)
    useful = sum(int(sp.stat("rows")) * ctx.steps(tier) * unet[tier]
                 for tier, _b, sp in calls)
    useful += sum(int(sp.stat("rows")) * disc
                  for _b, sp in ctx.traced_disc_calls())
    lo, hi = ctx.trace_window
    return 100.0 * useful / (hi - lo) / ctx.peak["flops_per_s"]
