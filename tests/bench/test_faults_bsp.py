"""A run whose timed path is broken underneath comes out not correct:
BSP with its state left unchanged, half of each batch left out, or the
exchange (the mean over sites) left out."""
import jax
import pytest
from jax.tree_util import tree_map

from cpu_run import run_cell

CELL = "bn-lenet.bsp.k5"


def stale_state(monkeypatch):
    from repro.core.algorithms.bsp import BSP
    step = BSP.step

    def frozen(self, state, *a, **k):
        return state, step(self, state, *a, **k)[1]
    monkeypatch.setattr(BSP, "step", frozen)


def half_batch(monkeypatch):
    from repro.core import trainer
    from repro.core.algorithms.base import ModelFns
    make = trainer.make_cnn_fns

    def halved(cfg):
        fns, ev = make(cfg)
        lg = fns.loss_and_grad
        return ModelFns(loss_and_grad=lambda p, s, b: lg(
            p, s, {k: v[: v.shape[0] // 2] for k, v in b.items()})), ev
    monkeypatch.setattr(trainer, "make_cnn_fns", halved)


def no_exchange(monkeypatch):
    from repro.core.algorithms import bsp
    monkeypatch.setattr(bsp, "tree_mean0",
                        lambda t: tree_map(lambda l: l[0], t))


@pytest.mark.parametrize("fault", [stale_state, half_batch, no_exchange])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()
    res = run_cell(monkeypatch, CELL)
    jax.clear_caches()
    assert not res["correct"], res["checks"]
