"""Operations and bytes the algorithm needs, as functions of shapes.

The UNet forward's operations are its convolutions, dense layers and
attention products, counted as 2 per multiply-add at the shapes the
configuration gives (normalisations and activations are left out: they
are a few percent of the total). A conv's work is its algorithmic work,
2 * C_in * C_out per sample for each kernel tap that lands inside the
input, whichever route
(implicit GEMM or im2col) the program runs it on.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table
    is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def _taps(n_in: int, k: int, stride: int) -> int:
    """Kernel taps that land inside the input along one axis, summed over
    the outputs of a SAME-padded conv (padding adds no work)."""
    n_out = -(-n_in // stride)
    lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for kk in range(k)
               if 0 <= o * stride + kk - lo < n_in)


def conv_flops(h: int, w: int, k: int, stride: int, c_in: int,
               c_out: int) -> float:
    return 2.0 * _taps(h, k, stride) * _taps(w, k, stride) * c_in * c_out


def matmul_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def w4a4_matmul_bytes(m: int, k: int, n: int) -> float:
    """f32 activations in, packed 4-bit weight (two codes a byte) and a
    per-column f32 scale and zero point, f32 out."""
    return 4.0 * m * k + k * n / 2 + 8.0 * n + 4.0 * m * n


def w4a4_conv_bytes(h: int, w: int, h_out: int, w_out: int, k: int,
                    c_in: int, c_out: int) -> float:
    """The conv's least traffic: its f32 input once, its packed weight,
    its f32 output."""
    return (4.0 * h * w * c_in + k * k * c_in * c_out / 2 + 8.0 * c_out
            + 4.0 * h_out * w_out * c_out)


def unet_layers(m: dict) -> list[dict]:
    """Every conv, dense and attention product of one sample's forward,
    with its shape: ``{"kind": "conv"|"dense"|"attn", ...}``. The io convs
    (``"io": True``) keep dense weights and are not W4A4 kernels."""
    ch, temb = m["ch"], 4 * m["ch"]
    out: list[dict] = []

    def conv(res, c_in, c_out, k=3, stride=1, io=False):
        out.append({"kind": "conv", "h": res, "w": res, "stride": stride,
                    "h_out": res // stride, "w_out": res // stride, "k": k,
                    "c_in": c_in, "c_out": c_out, "io": io})

    def dense(rows, k, n):
        out.append({"kind": "dense", "m": rows, "k": k, "n": n})

    def res(px, c_in, c_out):
        conv(px, c_in, c_out)
        dense(1, temb, c_out)
        conv(px, c_out, c_out)
        if c_in != c_out:
            conv(px, c_in, c_out, k=1)

    def attn(px, c):
        for _ in range(4):
            dense(px * px, c, c)
        out.append({"kind": "attn", "tokens": px * px, "c": c})

    dense(1, ch, temb)
    dense(1, temb, temb)
    px = m["image_size"]
    conv(px, m["in_ch"], ch, io=True)
    chans, c = [ch], ch
    levels = len(m["ch_mult"])
    for i, mult in enumerate(m["ch_mult"]):
        for _ in range(m["num_res_blocks"]):
            res(px, c, ch * mult)
            c = ch * mult
            if px in m["attn_resolutions"]:
                attn(px, c)
            chans.append(c)
        if i != levels - 1:
            conv(px, c, c, stride=2)
            px //= 2
            chans.append(c)
    res(px, c, c)
    attn(px, c)
    res(px, c, c)
    for i in reversed(range(levels)):
        for _ in range(m["num_res_blocks"] + 1):
            c_skip = chans.pop()
            res(px, c + c_skip, ch * m["ch_mult"][i])
            c = ch * m["ch_mult"][i]
            if px in m["attn_resolutions"]:
                attn(px, c)
        if i != 0:
            px *= 2
            conv(px, c, c)
    conv(px, c, m["out_ch"], io=True)
    return out


def layer_flops(layer: dict) -> float:
    if layer["kind"] == "conv":
        return conv_flops(layer["h"], layer["w"], layer["k"],
                          layer["stride"], layer["c_in"], layer["c_out"])
    if layer["kind"] == "dense":
        return matmul_flops(layer["m"], layer["k"], layer["n"])
    # q k^T and the weighted sum of v
    return 2 * matmul_flops(layer["tokens"], layer["c"], layer["tokens"])


def unet_flops(m: dict) -> float:
    """Operations of one sample's UNet forward."""
    return sum(layer_flops(x) for x in unet_layers(m))


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
