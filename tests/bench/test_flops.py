"""Operation and byte counts against hand counts."""
import json
import os

from benchlib import flops
from benchlib.registry import BENCH_DIR


def lenet():
    with open(os.path.join(BENCH_DIR, "configs", "bn-lenet.json")) as f:
        return json.load(f)


def test_lenet_flops_hand_count():
    # conv 5x5: 3->32 at 32x32, 32->32 at 16x16, 32->64 at 8x8; fc
    # 1024->64, out 64->10; two FLOPs per multiply-add
    fwd = (2 * 25 * 3 * 32 * 32 * 32 + 2 * 25 * 32 * 32 * 16 * 16
           + 2 * 25 * 32 * 64 * 8 * 8 + 2 * 1024 * 64 + 2 * 64 * 10)
    assert flops.lenet_forward_flops(lenet()) == fwd == 24_708_352
    assert flops.lenet_train_flops_per_image(lenet()) == 3 * fwd


def test_reference_lenet_has_the_published_parameter_count():
    from benchlib.registry import load_module
    ref = load_module(os.path.join(BENCH_DIR, "reference", "cnn_train.py"),
                      "ref_cnn_train")
    params = ref.init_params(0, lenet())
    assert sum(v.size for v in params.values()) == lenet()[
        "params_per_site"] == 145_834
