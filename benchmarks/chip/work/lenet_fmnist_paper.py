"""Work of one simulated round of the paper's LeNet deployment, from shapes.

Model FLOPs count 2 per multiply-add of the convolutions and products:
forward for every sample; backward as the weight gradient of every layer
plus the input gradient of every layer but the first (the images need
none). Elementwise work (tanh, pooling, the loss) is left out. A round
trains K clients for M steps of B samples and probes K * B samples
forward (eq. 4).
"""
from __future__ import annotations


def macs(cfg: dict) -> list:
    """Multiply-adds per sample, layer by layer."""
    h, w, c = cfg["input_shape"]
    ks = cfg["kernel_size"]
    out = []
    for cout in (cfg["conv1_channels"], cfg["conv2_channels"]):
        h, w = h - ks + 1, w - ks + 1
        out.append(h * w * cout * ks * ks * c)
        h, w, c = h // 2, w // 2, cout
    din = h * w * c
    for dout in (*cfg["fc_widths"], cfg["num_classes"]):
        out.append(din * dout)
        din = dout
    return out


def forward_flops(cfg: dict) -> int:
    return 2 * sum(macs(cfg))


def train_flops(cfg: dict) -> int:
    """Forward + backward of one sample."""
    m = macs(cfg)
    return 2 * sum(m) + 2 * sum(m) + 2 * sum(m[1:])


def n_params(cfg: dict) -> int:
    h, w, c = cfg["input_shape"]
    ks = cfg["kernel_size"]
    total = 0
    for cout in (cfg["conv1_channels"], cfg["conv2_channels"]):
        total += ks * ks * c * cout + cout
        h, w, c = (h - ks + 1) // 2, (w - ks + 1) // 2, cout
    din = h * w * c
    for dout in (*cfg["fc_widths"], cfg["num_classes"]):
        total += din * dout + dout
        din = dout
    return total


def per_round(cfg: dict, traffic: dict) -> dict:
    k, m, b = cfg["k"], cfg["local_steps"], cfg["batch_size"]
    flops = k * m * b * train_flops(cfg) + k * b * forward_flops(cfg)
    return {"flops": flops, "bytes": 0}
