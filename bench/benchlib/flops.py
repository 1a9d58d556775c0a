"""Operations and bytes the algorithm needs, from the shapes alone.

Model FLOPs count the forward and backward passes that training
requires (three times the forward matmul and convolution work);
recomputation is not counted.
"""
from __future__ import annotations

from typing import Dict


def lenet_forward_flops(cfg: Dict) -> int:
    """FLOPs of one image's forward pass: convolutions ('SAME', stride 1)
    and dense layers, 2 per multiply-add; norms, pools and activations
    are left out as negligible."""
    side, c_in, total = cfg["image_size"], cfg["in_channels"], 0
    for c, k, pool in zip(cfg["conv_channels"], cfg["kernel_sizes"],
                          cfg["pool_after"]):
        total += 2 * k * k * c_in * c * side * side
        if pool:
            side //= 2
        c_in = c
    d = side * side * c_in
    for fd in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        total += 2 * d * fd
        d = fd
    return total


def lenet_train_flops_per_image(cfg: Dict) -> int:
    return 3 * lenet_forward_flops(cfg)
