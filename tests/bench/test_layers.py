"""The reduction from the program's spans and scopes to a round's time by
layer (``benchlib/layers.py``), on hand-made events and on a short trace
recorded on the chip."""
import gzip
import json
import os

import pytest

from benchlib import layers
from benchlib.registry import BENCH_DIR

MS = 1e6    # ns
STEP = "jit_step"


def events(ops, host=()):
    """One chip's ``[name, start, dur, path, module]`` ops in a 100 ms
    window, and host spans ``[name, start, dur, round]``."""
    return {"devices": {"/device:TPU:0": [list(o) for o in ops]},
            "host": [["bench.window", 0.0, 100 * MS, None]] +
            [list(h) for h in host]}


@pytest.mark.parametrize("path, want", [
    ("jit(step)/local_step/vmap(jvp(conv))", "local_step"),
    ("jit(step)/exchange/jit(_gaia_pallas)/pallas_call", "exchange"),
    ("jit(step)/transpose(jvp(local_step))/dot_general", "local_step"),
    ("jit(_step)/exchange/local_step_x", "exchange"),
    ("jit(step)/reduce_sum", None),
    ("my_exchange/local_steps", None),
    ("", None),
])
def test_scope_of_finds_the_outermost_scope(path, want):
    assert layers.scope_of(path) == want


def test_busy_time_by_scope_and_what_no_scope_covers():
    ops = [["fusion.1", 10 * MS, 20 * MS, "jit(step)/local_step/a", STEP],
           ["fusion.2", 25 * MS, 10 * MS, "jit(step)/local_step/b", STEP],
           ["gaia_select.3", 40 * MS, 5 * MS, "jit(step)/exchange/c", STEP],
           ["copy.4", 44 * MS, 4 * MS, "", STEP],
           ["fusion.5", 95 * MS, 10 * MS, "jit(step)/exchange/d", STEP]]
    s = layers.by_scope(events(ops))
    # local [10,35]; exchange [40,45] + [95,100]; copy [44,48] less the
    # exchange's [44,45]; busy [10,35] + [40,48] + [95,100]
    assert s == pytest.approx({"local_step": 0.025, "exchange": 0.010,
                               "unscoped": 0.003, "busy": 0.038})


def test_busy_time_by_scope_is_averaged_over_chips():
    ev = events([["fusion.1", 0.0, 40 * MS, "jit(step)/exchange", STEP]])
    ev["devices"]["/device:TPU:1"] = [["fusion.1", 0.0, 20 * MS, "", STEP]]
    s = layers.by_scope(ev, 2)
    assert s["exchange"] == pytest.approx(0.020)
    assert s["unscoped"] == pytest.approx(0.010)
    with pytest.raises(RuntimeError):
        layers.by_scope(ev, 3)


def test_busy_time_inside_each_program_span():
    ops = [["fusion.1", 5 * MS, 10 * MS, "", STEP],
           ["fusion.2", 30 * MS, 20 * MS, "", STEP]]
    host = [["trainer.dispatch", 0.0, 10 * MS, 1],
            ["trainer.wait", 10 * MS, 30 * MS, 1],
            ["trainer.dispatch", 45 * MS, 10 * MS, 2],
            ["Transpose", 0.0, 50 * MS, None]]
    s = layers.inside(events(ops, host))
    # dispatch [0,10] + [45,55] hold [5,10] and [45,50]; wait [10,40]
    # holds [10,15] and [30,40]; a runtime event is no program span
    assert s == pytest.approx({"trainer.dispatch": 0.010,
                               "trainer.wait": 0.015})


def test_kernel_events_apart_from_the_other_ops_by_scope():
    ops = [["gaia_select.3", 10 * MS, 1 * MS, "jit(step)/exchange/p", STEP],
           ["gaia_select.4", 12 * MS, 2 * MS, "jit(step)/exchange/p", STEP],
           ["reshape.5", 15 * MS, 3 * MS, "jit(step)/exchange/r", STEP],
           ["neighbor_mix.1", 20 * MS, 4 * MS, "", STEP],
           ["fusion.2", 30 * MS, 5 * MS, "jit(step)/local_step/f", STEP],
           ["fusion.2", 101 * MS, 5 * MS, "jit(step)/local_step/f", STEP]]
    sizes = {"gaia_select.3": (30, 20), "gaia_select.4": (3, 2),
             "reshape.5": (7, 7)}
    s = layers.kernel_split(events(ops), sizes)
    # the last op starts after the window; fusion.2 has no size
    assert s == {
        "exchange": {"kernel": pytest.approx({"n": 2, "seconds": 0.003,
                                              "read": 33, "written": 22}),
                     "other": pytest.approx({"n": 1, "seconds": 0.003,
                                             "read": 7, "written": 7})},
        None: {"kernel": pytest.approx({"n": 1, "seconds": 0.004,
                                        "read": 0, "written": 0})},
        "local_step": {"other": pytest.approx({"n": 1, "seconds": 0.005,
                                               "read": 0, "written": 0})}}


def test_kernel_split_is_averaged_over_chips():
    ev = events([["gaia_select.3", 0.0, 4 * MS, "a/exchange", STEP]])
    ev["devices"]["/device:TPU:1"] = [
        ["gaia_select.3", 0.0, 2 * MS, "a/exchange", STEP],
        ["gaia_select.3", 5 * MS, 2 * MS, "a/exchange", STEP]]
    got = layers.kernel_split(ev, {"gaia_select.3": (8, 4)}, 2)
    assert got["exchange"]["kernel"] == pytest.approx(
        {"n": 1.5, "seconds": 0.004, "read": 12, "written": 6})


def test_twins_are_matched_by_name_and_round_as_often_as_recorded():
    host = [["trainer.put", 0.0, 1.0, 3], ["trainer.put", 2.0, 1.0, 3],
            ["trainer.wait", 4.0, 1.0, 3], ["trainer.wait", 9.0, 1.0, 4],
            ["Transpose", 5.0, 1.0, None]]
    mine = [("trainer.put", 3), ("trainer.put", 3), ("trainer.wait", 3)]
    assert layers.twins(mine, host) == []
    assert layers.twins(mine + [("trainer.put", 3), ("trainer.load", 4)],
                        host) == [("trainer.load", 4), ("trainer.put", 3)]


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0: f32[5]) -> f32[5] {
  %param_0 = f32[5]{0} parameter(0)
  ROOT %neg.1 = f32[5]{0} negate(%param_0), metadata={op_name="jit(step)/local_step/neg"}
}

ENTRY %main.9 (p: f32[5]) -> f32[5] {
  %p = f32[5]{0} parameter(0), metadata={op_name="state"}
  %fusion.2 = f32[5]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/local_step/neg" stack_frame_id=2}
  %gaia_select.7 = f32[5]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/exchange/pallas_call" stack_frame_id=3}
  ROOT %copy.3 = f32[5]{0} copy(%gaia_select.7)
}
"""


def test_scopes_from_the_compiled_program_text():
    module, paths = layers.hlo_scopes(HLO)
    assert module == STEP
    assert paths["fusion.2"] == "jit(step)/local_step/neg"
    assert paths["gaia_select.7"] == "jit(step)/exchange/pallas_call"
    assert "copy.3" not in paths
    with pytest.raises(ValueError):
        layers.hlo_scopes("ENTRY %main {}")


def test_bytes_each_instruction_reads_and_writes():
    got = layers.hlo_bytes(HLO)
    assert got["p"] == (0, 20)
    assert got["fusion.2"] == (20, 20)
    assert got["gaia_select.7"] == (20, 20)
    assert got["neg.1"] == (20, 20)        # a fused computation's own


@pytest.mark.parametrize("line, want", [
    ("  %c.1 = (f32[5,8,128]{2,1,0:T(8,128)}, s32[8,128]{1,0:T(8,128)}) "
     "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
     (5 * 8 * 128 * 4 + 8 * 128 * 4, ["a", "b"])),
    ("  ROOT %t.2 = (pred[3], bf16[2,2]{1,0}) tuple(%a)", (3 + 8, ["a"])),
    ("  %k.3 = f32[] constant(0)", (4, [])),
    ("  %r.4 = f64[2,0]{1,0} copy-start(%a), cross_program_prefetch_index=0",
     (0, ["a"])),
])
def test_bytes_of_an_instruction_line(line, want):
    written, operands = want
    text = "\n".join(f"  %{o} = f32[3]{{0}} parameter({i})"
                     for i, o in enumerate(operands)) + "\n" + line
    got = layers.hlo_bytes(text)
    name = line.split("%", 1)[1].split(" ", 1)[0]
    assert got[name] == (12 * len(operands), written)


def test_attach_fills_only_the_ops_of_that_module():
    ev = events([["fusion.2", 0.0, 1.0, "", STEP],
                 ["fusion.2", 2.0, 1.0, "", "jit_convert_element_type"],
                 ["gaia_select.7", 4.0, 1.0, "kept/exchange", STEP],
                 ["copy.3", 6.0, 1.0, "", STEP]])
    layers.attach(ev, *layers.hlo_scopes(HLO))
    assert [o[3] for o in ev["devices"]["/device:TPU:0"]] == [
        "jit(step)/local_step/neg", "", "kept/exchange", ""]


def test_each_op_gets_the_module_whose_run_holds_it():
    ops = [["a.1", 5.0, 1.0, ""], ["b.1", 15.0, 1.0, ""],
           ["c.1", 30.0, 1.0, ""], ["d.1", 1.0, 1.0, ""]]
    layers._module_of(ops, [(10.0, 20.0, "jit_convert"),
                            (2.0, 9.0, STEP)])
    assert [o[4] for o in ops] == [STEP, "jit_convert", "", ""]


# ------------------------------------------------- a trace from the chip

#: 80 ms of a traced window of ``bench/round_split.py`` on a TPU v5e
#: (bn-lenet.gaia.k5): the step's ops with their scopes attached from the
#: compiled program, the program's spans and the window
RECORDED = os.path.join(BENCH_DIR, "testdata",
                        "bn-lenet.gaia.k5.layers80ms.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def _by_hand(ev):
    """Per-scope time of the one chip as the plain sum of its ops'
    durations in the window: the ops of this trace do not overlap, so the
    sum is the union."""
    (ops,) = ev["devices"].values()
    ops = sorted(ops, key=lambda o: o[1])
    assert all(b[1] >= a[1] + a[2] for a, b in zip(ops, ops[1:]))
    t0, t1 = layers.window_of(ev["host"])
    out = {}
    for _, s, d, path, _ in ops:
        key = layers.scope_of(path) or "unscoped"
        out[key] = out.get(key, 0.0) + max(0.0, min(s + d, t1) - max(s, t0))
    return {k: v / 1e9 for k, v in out.items()}


def test_recorded_trace_scopes_against_a_hand_count(recorded):
    s = layers.by_scope(recorded)
    hand = _by_hand(recorded)
    for k in ("local_step", "exchange", "unscoped"):
        assert s[k] == pytest.approx(hand.get(k, 0.0), rel=1e-9, abs=1e-12)
    assert s["busy"] == pytest.approx(sum(hand.values()), rel=1e-9)
    # the step's work is nearly all under a scope
    assert s["unscoped"] < 0.1 * s["busy"]


def test_recorded_trace_puts_every_gaia_kernel_under_the_exchange(recorded):
    (ops,) = recorded["devices"].values()
    kernels = [o for o in ops if o[0].startswith("gaia_select.")]
    assert kernels
    assert {layers.scope_of(o[3]) for o in kernels} == {"exchange"}


def test_recorded_trace_kernel_events_against_a_hand_count(recorded):
    s = layers.kernel_split(recorded, {})
    (ops,) = recorded["devices"].values()
    t0, t1 = layers.window_of(recorded["host"])
    mine = [o for o in ops if o[0].startswith("gaia_select.")
            and t0 <= o[1] < t1]
    assert set(s) == {"local_step", "exchange", None}
    assert "kernel" not in s["local_step"] and "kernel" not in s[None]
    assert s["exchange"]["kernel"]["n"] == len(mine) == 32
    assert s["exchange"]["kernel"]["seconds"] == pytest.approx(
        sum(o[2] for o in mine) / 1e9)
    # the kernel and the other ops of the exchange make up its busy time
    ex = s["exchange"]
    assert ex["kernel"]["seconds"] + ex["other"]["seconds"] == \
        pytest.approx(layers.by_scope(recorded)["exchange"], rel=1e-6)


def test_recorded_trace_busy_inside_spans(recorded):
    ins = layers.inside(recorded)
    s = layers.by_scope(recorded)
    assert set(ins) >= {"trainer.round", "trainer.dispatch",
                        "trainer.wait"}
    # the spans of a round hold at most the chip's busy time, and the
    # wait on the step holds the most of it
    assert all(v <= s["busy"] * (1 + 1e-12) for v in ins.values())
    assert ins["trainer.wait"] == max(
        v for k, v in ins.items() if k != "trainer.round")
