"""The whole UNet step's share of the chip's peak: the forward's
operations per sample (``flops.unet_flops``) times the sample-forwards
the engine dispatched in the traced window (a guided request's two count
as two; padding rows do not count), over the window times the peak."""
import flops


def read(ctx):
    red = ctx.reduction
    if red is None or red.window_s <= 0:
        return None
    t0, t1 = ctx.window["traced"][1] * 1e6, ctx.window["traced"][3] * 1e6
    samples = sum(e["args"]["items"] for e in ctx.events
                  if e.get("ph") == "X" and e["name"] == "forward"
                  and t0 <= e["ts"] <= t1)
    if not samples:
        return None
    peak = flops.peaks(ctx.device_kind)
    work = flops.unet_flops(ctx.cell.cfg["model"]) * samples
    return 100.0 * work / (red.window_s * peak["bf16_flops_per_s"])
