"""The CNN driver and the entry point, run on the CPU at a tiny size."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from cpu_run import BENCH_DIR, driver_ctx, run_cell, small_cell

ROOT = os.path.dirname(BENCH_DIR)
CELL = "bn-lenet.bsp.k5"


@pytest.mark.parametrize("traced", [False, True])
def test_window_arithmetic(monkeypatch, tmp_path, traced):
    """The window's counters are those of its untraced rounds; a trace,
    asked for, covers the rounds run after the window."""
    monkeypatch.setenv("REPRO_DISPATCH_CACHE", "")
    cell = small_cell(CELL)
    ctx = driver_ctx(cell, seconds=1.5)
    ctx.trace_dir = str(tmp_path) if traced else None
    drv = cell.driver()
    out = drv.run(ctx)
    c = out["counters"]
    rounds, steps = np.asarray(c["round_s"]), np.asarray(c["step_s"])
    assert c["rounds"] == out["attempted"] == len(rounds) == len(steps) >= 3
    # the window is the rounds between its stamps, each holding its step
    assert rounds.sum() == pytest.approx(c["window_s"], rel=1e-9)
    assert np.all(rounds > steps)
    m = out["metrics"]
    assert m["train_images_per_s"] == pytest.approx(
        c["rounds"] * 100 / c["window_s"])
    assert m["round_ms_p95"] == pytest.approx(
        np.percentile(rounds, 95) * 1e3)
    assert 0 < m["setup_s"]
    assert out["compiles_in_window"] == 0
    assert out["failed"] == 0
    for chk in out["checks"]:
        assert chk["value"] <= chk["limit"], chk
    if traced:
        from benchlib import trace
        assert c["traced_rounds"] == drv.TRACED
        host = trace.load(str(tmp_path))["host"]
        t0, t1 = trace.window_of(host)
        spans = [s for n, s, d, _ in host if n == trace.ROUND]
        assert len(spans) == drv.TRACED
        assert all(t0 <= s < t1 for s in spans)
    else:
        assert "traced_rounds" not in c


def test_sound_run_is_correct(monkeypatch):
    res = run_cell(monkeypatch, CELL)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_images_per_s", "round_ms_p95",
                                   "setup_s"}


def _bench(args, env=None, cwd=ROOT):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           CELL, "--seed", "3", "--seconds", "1",
                           "--trace", "0", *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env", [{}, {"REPRO_KERNEL_DISPATCH": "oracle"},
                                 {"REPRO_KERNEL_DISPATCH_GAIA_SELECT":
                                  "pallas"}])
def test_no_tpu_or_a_dispatch_override_prints_no_result(env):
    p = _bench([], env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench([], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/repro" in p.stderr
