"""Model FLOPs of the paper's CNN, counted from its widths.

A multiply-add is 2 FLOPs. Biases, activations, pooling and the loss
are left out: they are a fraction of a percent of the convolutions.
"""
from __future__ import annotations


def cnn_forward_flops(cfg: dict) -> int:
    """FLOPs of one sample's forward pass (SAME 5x5 convs, each
    followed by 2x2 pooling, then three dense layers)."""
    s = cfg["side"]
    c1, c2 = cfg["conv_channels"]
    f1, f2 = cfg["fc_dims"]
    taps = cfg["conv_kernel"] ** 2
    conv1 = 2 * s * s * taps * 1 * c1
    conv2 = 2 * (s // 2) ** 2 * taps * c1 * c2
    flat = (s // 4) ** 2 * c2
    dense = 2 * (flat * f1 + f1 * f2 + f2 * cfg["num_classes"])
    return conv1 + conv2 + dense


def round_flops(cfg: dict, n_selected: int) -> int:
    """One round's model FLOPs: a forward for each of the K·|D̂| scored
    samples (sigma), and forward plus backward, three forwards, for
    each selected sample (the eq. 4 local gradients)."""
    fwd = cnn_forward_flops(cfg)
    return fwd * (cfg["K"] * cfg["d_hat"] + 3 * int(n_selected))
