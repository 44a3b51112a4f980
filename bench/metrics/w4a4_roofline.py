"""The W4A4 kernels' share of their roofline: the least time of every
fused W4A4 matmul and conv the window's forwards ran (``flops.py``
operations and bytes at the padded batch they ran at, against the peaks
table), over the device time of the two Pallas kernels in the trace
(``w4a4_matmul_2d``, which also runs the im2col route's GEMMs, and
``w4a4_conv2d_implicit``). The io convs keep dense weights and run on
XLA's conv; they are left out of both sides."""
import flops

KERNELS = ("w4a4_matmul_2d", "w4a4_conv2d_implicit")


def least_seconds_per_forward(m: dict, rows: int, peak: dict) -> float:
    total = 0.0
    for layer in flops.unet_layers(m):
        if layer["kind"] == "dense":
            rows_l = layer["m"] * rows
            total += flops.least_seconds(
                flops.matmul_flops(rows_l, layer["k"], layer["n"]),
                flops.w4a4_matmul_bytes(rows_l, layer["k"], layer["n"]), peak)
        elif layer["kind"] == "conv" and not layer["io"]:
            total += flops.least_seconds(
                rows * flops.layer_flops(layer),
                rows * flops.w4a4_conv_bytes(
                    layer["h"], layer["w"], layer["h_out"], layer["w_out"],
                    layer["k"], layer["c_in"], layer["c_out"]), peak)
    return total


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    device = sum(red.op_seconds.get(k, 0.0) for k in KERNELS)
    t0, t1 = ctx.window["traced"][1] * 1e6, ctx.window["traced"][3] * 1e6
    rows = [e["args"]["padded_rows"] for e in ctx.events
            if e.get("ph") == "X" and e["name"] == "forward"
            and t0 <= e["ts"] <= t1]
    if device <= 0 or not rows:
        return None
    peak = flops.peaks(ctx.device_kind)
    m = ctx.cell.cfg["model"]
    least = sum(least_seconds_per_forward(m, r, peak) for r in rows)
    return 100.0 * least / device
