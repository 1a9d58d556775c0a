"""The per-layer metric readers against hand counts, and silent where
they find nothing to read."""
from types import SimpleNamespace

import pytest

from benchlib.flops import lenet_train_flops_per_image
from benchlib.registry import load_cell

CELL = "bn-lenet.gaia.k5"
#: a window of 4 rounds of 12 ms, steps of 9 ms; 100 traced rounds in
#: which the chip was busy 0.45 s
COUNTERS = {"window_s": 0.048, "rounds": 4, "round_s": [0.012] * 4,
            "step_s": [0.009] * 4, "images_per_round": 100,
            "traced_rounds": 100}
TRACE = {"busy_s": 0.45, "window_s": 3.0}
PEAKS = {"bf16_flops_per_s": 197e12}


def run(**kw):
    cell = load_cell(CELL)
    base = dict(trace=TRACE, counters=COUNTERS, config=cell.config,
                traffic=cell.traffic, peaks=PEAKS, n_devices=1)
    return cell, SimpleNamespace(**dict(base, **kw))


@pytest.mark.parametrize("metric, want", [
    ("round_host_ms.cnn", 3.0),
    ("device_idle_share.cnn", 100 * (1 - 0.0045 / 0.012)),
    ("step_mfu.cnn", None),
])
def test_reader_by_hand(metric, want):
    cell, r = run()
    if want is None:                # step_mfu: FLOPs from the shapes
        want = 100 * lenet_train_flops_per_image(cell.config) * 100 / \
            0.012 / 197e12
    assert cell.reader(metric).read(r) == pytest.approx(want)


@pytest.mark.parametrize("metric, missing", [
    ("round_host_ms.cnn", dict(counters={})),
    ("device_idle_share.cnn", dict(trace=None)),
    ("device_idle_share.cnn",
     dict(counters={k: v for k, v in COUNTERS.items()
                    if k != "traced_rounds"})),
    ("step_mfu.cnn", dict(peaks=None)),
])
def test_reader_with_nothing_to_read_returns_nothing(metric, missing):
    cell, r = run(**missing)
    assert cell.reader(metric).read(r) is None
