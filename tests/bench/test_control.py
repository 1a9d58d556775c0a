"""The control, the plain reference one precision down put in the
program's place, and the faults planted in the reference come out not
correct; the program itself passes.

The control of a float32-at-``highest`` configuration is three bfloat16
passes (``high``).  On the CPU those passes lose less than on the chip,
where the limits were set (PERF.md section 6), so here the test holds
them to reading far above the program, and holds a bfloat16 reference,
further down, to the limits themselves."""
import pytest

from cpu_run import limits, small_cell

SEED = 2 ** 33 + 5


@pytest.mark.parametrize("name", ["bn-lenet.gaia.k5", "bn-lenet.bsp.k5"])
def test_control_and_faults_fail_and_program_passes(monkeypatch, name):
    monkeypatch.setenv("REPRO_DISPATCH_CACHE", "")
    import jax.numpy as jnp
    cell = small_cell(name)
    drv = cell.driver()
    tap, losses = drv.first_rounds(cell, SEED)
    r = drv.gaps(cell, SEED, tap, losses,
                 dict(drv.VARIANTS, bfloat16=dict(dtype=jnp.bfloat16)))
    lim = limits(name)
    over = lambda g: [k for k in lim if g[k] > lim[k]]
    assert over(r["program"]) == []
    for fault in ("half_batch", "no_exchange"):
        assert over(r[fault]), (fault, r[fault])
    assert any(r["control_high"][k] > 100 * max(r["program"][k], 1e-9)
               for k in lim), (r["control_high"], r["program"])
    assert over(r["bfloat16"]), r["bfloat16"]
