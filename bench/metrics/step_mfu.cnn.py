"""Model FLOP utilization of the whole CNN round: forward and backward
FLOPs per image (from the configuration's shapes) times images per
second over the window, over chips times the chip's bf16 peak, in %."""
from benchlib.flops import lenet_train_flops_per_image


def read(run):
    c = run.counters
    if not c.get("rounds") or run.peaks is None:
        return None
    rate = c["rounds"] * c["images_per_round"] / c["window_s"]
    flops = lenet_train_flops_per_image(run.config) * rate
    return 100.0 * flops / (run.n_devices * run.peaks["bf16_flops_per_s"])
