"""Static-analysis gate: planted violations are caught with the right
rule id (unkeyed np.random draw -> RA101, half-registered kernel op ->
PA301-304, untested rule id -> PA305, f32-widened bf16 exchange ->
GA202, off-axis permute -> GA201, host callback -> GA203, donation
drift -> GA204, plus the jaxpr-level JA400-405 twins caught before
lowering), suppression comments and the baseline grandfather findings,
and the real repo is clean under every pass."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis import (ALL_RULES, apply_baseline, astlint,
                            audit_hlo, audit_jaxpr, check_parity,
                            lint_file, load_baseline, write_baseline)
from repro.analysis.base import Finding, is_suppressed

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_snippet(tmp_path, code, name="planted.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    return lint_file(str(p), str(tmp_path))


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------- RA10x

class TestAstLint:
    def test_unkeyed_np_random_draw_is_ra101(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            x = np.random.uniform(size=8)
        """)
        assert rules_of(fs) == ["RA101"]
        assert "np.random.uniform" in fs[0].message

    def test_np_random_seed_is_ra101(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            np.random.seed(0)
        """)
        assert rules_of(fs) == ["RA101"]

    def test_argless_default_rng_is_ra101(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            from numpy.random import default_rng
            a = np.random.default_rng()
            b = default_rng()
        """)
        assert rules_of(fs) == ["RA101", "RA101"]

    def test_keyed_rng_constructions_pass(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            rng = np.random.default_rng(7)
            gen = np.random.Generator(np.random.PCG64(3))
            x = rng.uniform(size=8)
        """)
        assert fs == []

    def test_item_in_jitted_fn_is_ra102(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                return x.sum().item()
        """)
        assert rules_of(fs) == ["RA102"]

    def test_host_cast_of_param_in_jit_is_ra102(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def step(x, n):
                return x * float(n) + np.asarray(x)
        """)
        assert rules_of(fs) == ["RA102", "RA102"]

    def test_host_cast_outside_jit_passes(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def setup(x):
                return float(x)
        """)
        assert fs == []

    def test_jit_lambda_body_linted(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            f = jax.jit(lambda x: x.mean().item())
        """)
        assert rules_of(fs) == ["RA102"]

    def test_jit_call_in_loop_is_ra103(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            for lr in (0.1, 0.2):
                step = jax.jit(lambda x: x * lr)
        """)
        assert "RA103" in rules_of(fs)

    def test_jit_def_in_loop_is_ra103(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            while True:
                @jax.jit
                def step(x):
                    return x
        """)
        assert rules_of(fs) == ["RA103"]

    def test_nested_def_resets_loop_context(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            for _ in range(3):
                def make():
                    return jax.jit(lambda x: x)
        """)
        assert fs == []

    def test_broad_except_is_ra104(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            try:
                x = 1
            except Exception:
                pass
            try:
                y = 2
            except (ValueError, BaseException):
                pass
            try:
                z = 3
            except:
                pass
        """)
        assert rules_of(fs) == ["RA104", "RA104", "RA104"]

    def test_concrete_except_passes(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            try:
                x = 1
            except (OSError, ValueError):
                pass
        """)
        assert fs == []

    def test_syntax_error_is_ra100(self, tmp_path):
        fs = lint_snippet(tmp_path, "def broken(:\n")
        assert rules_of(fs) == ["RA100"]


class TestSuppression:
    def test_inline_allow_silences_rule(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            try:
                x = 1
            except Exception:  # repro-allow: RA104 — trial sweep
                pass
        """)
        assert fs == []

    def test_family_wildcard(self):
        assert is_suppressed("RA104", "pass  # repro-allow: RA*")
        assert not is_suppressed("GA201", "pass  # repro-allow: RA*")

    def test_allow_is_per_rule(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            x = np.random.uniform()  # repro-allow: RA104
        """)
        assert rules_of(fs) == ["RA101"]


class TestBaseline:
    def test_grandfather_and_expire(self, tmp_path):
        f1 = Finding(rule="RA104", path="a.py", line=3, message="m",
                     source="except Exception:")
        f2 = Finding(rule="RA101", path="b.py", line=9, message="m",
                     source="np.random.seed(0)")
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), [f1])
        fps = load_baseline(str(bl))
        apply_baseline([f1, f2], fps)
        assert f1.baselined and not f2.baselined
        # fingerprints are line-free: moving the finding keeps it known
        moved = Finding(rule="RA104", path="a.py", line=77, message="m",
                        source="except Exception:")
        apply_baseline([moved], fps)
        assert moved.baselined
        # but editing the flagged line expires the grandfather
        edited = Finding(rule="RA104", path="a.py", line=3, message="m",
                         source="except ValueError:")
        apply_baseline([edited], fps)
        assert not edited.baselined

    def test_stale_fingerprints_returned(self, tmp_path):
        f1 = Finding(rule="RA104", path="a.py", line=3, message="m",
                     source="except Exception:")
        f2 = Finding(rule="RA101", path="b.py", line=9, message="m",
                     source="np.random.seed(0)")
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), [f1, f2])
        fps = load_baseline(str(bl))
        # f2's flagged line was since fixed: its fingerprint is stale
        stale = apply_baseline([f1], fps)
        assert stale == [f2.fingerprint]
        assert f1.baselined
        # nothing stale when every entry still matches
        assert apply_baseline([f1, f2], fps) == []


# ---------------------------------------------------------------- PA30x

OPS_TEMPLATE = '''
import jax.numpy as jnp
from repro.kernels import ref as _ref


def _decide(op, *a, **k):
    return "oracle"


def wired_op(x):
    if _decide("wired_op", x.size) == "oracle":
        return _ref.wired_op_ref(x)
    return x


def half_op(x):
    return jnp.tanh(x)
'''

REF_TEMPLATE = '''
def wired_op_ref(x):
    return x
'''


def plant_tree(tmp_path, *, bench="ops.wired_op",
               test_body="wired_op"):
    """A minimal repo layout with one fully wired op and one half op."""
    k = tmp_path / "src" / "repro" / "kernels"
    k.mkdir(parents=True)
    (k / "ops.py").write_text(OPS_TEMPLATE)
    (k / "ref.py").write_text(REF_TEMPLATE)
    b = tmp_path / "benchmarks"
    b.mkdir()
    (b / "kernels_bench.py").write_text(f"ROWS = ['{bench}']\n")
    t = tmp_path / "tests"
    t.mkdir()
    (t / "test_planted.py").write_text(f"# exercises {test_body}\n")
    return str(tmp_path)


class TestParity:
    def test_half_registered_op_fails_all_four_legs(self, tmp_path):
        root = plant_tree(tmp_path)
        fs = check_parity(root)
        by_op = {}
        for f in fs:
            by_op.setdefault(f.source, []).append(f.rule)
        # wired_op PA304 passes because "wired_op" appears in the test;
        # half_op fails every leg except PA304 ("half_op" shares no
        # mention) — plant a test tree where it is mentioned nowhere
        assert "wired_op" not in by_op
        assert sorted(by_op["half_op"]) == ["PA301", "PA302", "PA303",
                                           "PA304"]

    def test_bench_row_and_test_reference_checked(self, tmp_path):
        root = plant_tree(tmp_path, bench="nothing",
                          test_body="half_op only")
        fs = check_parity(root)
        wired = sorted(f.rule for f in fs if f.source == "wired_op")
        assert wired == ["PA303", "PA304"]

    def test_missing_ops_module_is_single_finding(self, tmp_path):
        fs = check_parity(str(tmp_path))
        assert rules_of(fs) == ["PA301"]
        assert "not found" in fs[0].message

    def test_helper_indirection_resolves(self, tmp_path):
        """``_oracle = jit(_ref.x_ref)`` one level away still counts."""
        root = plant_tree(tmp_path)
        ops = (tmp_path / "src" / "repro" / "kernels" / "ops.py")
        ops.write_text('''
from repro.kernels import ref as _ref

_oracle = staticmethod(_ref.wired_op_ref)


def _decide(op):
    return "oracle"


def wired_op(x):
    _decide("wired_op")
    return _oracle(x)
''')
        fs = check_parity(root)
        assert not any(f.rule == "PA301" and f.source == "wired_op"
                       for f in fs)

    def test_untested_analysis_rule_is_pa305(self, tmp_path):
        root = plant_tree(tmp_path)
        (tmp_path / "tests" / "test_analysis.py").write_text(
            "# this planted gate only ever mentions RA101\n")
        pa305 = {f.source for f in check_parity(root)
                 if f.rule == "PA305"}
        # every registered rule the planted file omits is flagged...
        assert {"GA202", "JA402", "PA305"} <= pa305
        # ...but the one it mentions is not
        assert "RA101" not in pa305

    def test_pa305_skipped_without_analysis_tests(self, tmp_path):
        # the default planted tree has no tests/test_analysis.py: the
        # meta-rule must not red-herring a partial layout
        root = plant_tree(tmp_path)
        assert not any(f.rule == "PA305" for f in check_parity(root))


# ---------------------------------------------------------------- GA20x

HLO_HEAD = ("HloModule planted, input_output_alias={ {0}: (0, {}, "
            "may-alias) }\n\n")

HLO_GOOD = HLO_HEAD + """\
ENTRY %main (p0: bf16[8,8]) -> (bf16[8,8]) {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %cp = bf16[8,8]{1,0} collective-permute(%p0), source_target_pairs={{0,2},{2,0},{1,3},{3,1}}
  ROOT %out = (bf16[8,8]{1,0}) tuple(%cp)
}
"""


def planted_hlo(*, dtype="bf16", pairs="{{0,2},{2,0},{1,3},{3,1}}",
                extra="", alias=True, out_dtype=None):
    out_dtype = out_dtype or dtype
    head = HLO_HEAD if alias else "HloModule planted\n\n"
    return head + f"""\
ENTRY %main (p0: bf16[8,8]) -> ({out_dtype}[8,8]) {{
  %p0 = bf16[8,8]{{1,0}} parameter(0)
  %cv = {dtype}[8,8]{{1,0}} convert(%p0)
  %cp = {dtype}[8,8]{{1,0}} collective-permute(%cv), source_target_pairs={pairs}
{extra}  ROOT %out = ({out_dtype}[8,8]{{1,0}}) tuple(%cp)
}}
"""


class TestGraphAudit:
    def test_clean_gossip_step_passes(self):
        ga = audit_hlo(HLO_GOOD, devices_per_pod=2, expect_donation=True)
        assert ga.ok, [f.format() for f in ga.findings]
        assert ga.expected_wire_dtype == "bf16"
        assert ga.pod_exchange.pod_axis_only
        assert ga.donated_pairs == 1

    def test_widened_wire_dtype_is_ga202(self):
        # bf16 leaf, f32 on the wire: the adpsgd payload bug from PR 4
        ga = audit_hlo(planted_hlo(dtype="f32", out_dtype="f32",
                                   alias=False),
                       devices_per_pod=2)
        assert [f.rule for f in ga.findings] == ["GA202"]
        assert "bf16" in ga.findings[0].message
        assert ga.cross_pod_dtype_bytes == {"f32": 256.0}

    def test_off_pod_axis_permute_is_ga201(self):
        # 0->3 crosses pods AND changes the intra-pod coordinate
        ga = audit_hlo(planted_hlo(pairs="{{0,3},{3,0}}"),
                       devices_per_pod=2)
        assert "GA201" in [f.rule for f in ga.findings]

    def test_host_callback_is_ga203(self):
        extra = ('  %cb = bf16[8,8]{1,0} custom-call(%p0), '
                 'custom_call_target="xla_python_cpu_callback"\n')
        ga = audit_hlo(planted_hlo(extra=extra), devices_per_pod=2)
        assert "GA203" in [f.rule for f in ga.findings]
        assert ga.host_callbacks == ["xla_python_cpu_callback"]

    def test_infeed_is_ga203(self):
        extra = "  %inf = ((bf16[8,8]{1,0}), token[]) infeed(%p0)\n"
        ga = audit_hlo(planted_hlo(extra=extra), devices_per_pod=2)
        assert "GA203" in [f.rule for f in ga.findings]

    def test_missing_alias_map_is_ga204_only_when_expected(self):
        ga = audit_hlo(planted_hlo(alias=False), devices_per_pod=2,
                       expect_donation=True)
        assert [f.rule for f in ga.findings] == ["GA204"]
        ga2 = audit_hlo(planted_hlo(alias=False), devices_per_pod=2)
        assert ga2.ok

    def test_output_type_drift_is_ga204(self):
        # donated param is bf16 but the aliased output comes back f32:
        # step t's output cannot feed step t+1 without a realloc
        ga = audit_hlo(planted_hlo(dtype="f32", out_dtype="f32",
                                   pairs="{{0,1},{1,0}}"),
                       devices_per_pod=4)  # single pod: no GA202
        assert [f.rule for f in ga.findings] == ["GA204"]
        assert "drift" in ga.findings[0].message

    def test_unclassifiable_collective_is_ga205(self):
        extra = ("  %s = (bf16[8,8]{1,0}, u32[], token[]) send(%p0), "
                 "channel_id=1\n")
        ga = audit_hlo(planted_hlo(extra=extra), devices_per_pod=2)
        assert "GA205" in [f.rule for f in ga.findings]

    def test_to_json_shape(self):
        j = audit_hlo(HLO_GOOD, devices_per_pod=2).to_json()
        assert j["ok"] and j["pod_exchange"]["devices_per_pod"] == 2
        assert set(j) >= {"tag", "findings", "expected_wire_dtype",
                          "host_callbacks", "donated_pairs"}


# ---------------------------------------------------------------- JA4xx

POD_ENV = [("pod", 2)]
PERM = [(0, 1), (1, 0)]


def jaxpr_of(fn, *avals, axis_env=None):
    return jax.make_jaxpr(fn, axis_env=axis_env or POD_ENV)(*avals)


def aval(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestJaxprAudit:
    def test_clean_gossip_like_step_passes(self):
        def step(x):
            return lax.ppermute(x, "pod", PERM)
        a = audit_jaxpr(jaxpr_of(step, aval(8, 8, dtype=jnp.bfloat16)))
        assert a.ok, [f.format() for f in a.findings]
        assert a.n_collectives == 1 and a.collective_axes == ["pod"]

    def test_debug_print_is_ja401(self):
        def step(x):
            jax.debug.print("loss {}", x.sum())
            return x * 2
        a = audit_jaxpr(jaxpr_of(step, aval(4)))
        assert "JA401" in [f.rule for f in a.findings]

    def test_pure_callback_is_ja401(self):
        def step(x):
            return jax.pure_callback(lambda v: v, aval(4), x)
        a = audit_jaxpr(jaxpr_of(step, aval(4)))
        assert "JA401" in [f.rule for f in a.findings]

    def test_widen_into_collective_is_ja402(self):
        # the adpsgd wire bug, pre-lowering: a bf16 leaf widened to f32
        # right before the exchange — XLA would fold the convert into
        # the collective lowering, the jaxpr still shows it
        def step(x):
            return lax.ppermute(x.astype(jnp.float32), "pod", PERM)
        a = audit_jaxpr(jaxpr_of(step, aval(8, 8, dtype=jnp.bfloat16)))
        assert [f.rule for f in a.findings] == ["JA402"]
        assert "convert_element_type" in a.findings[0].message

    def test_accumulate_then_narrow_is_clean(self):
        # the legitimate pattern: accumulate in f32, narrow back to the
        # leaf dtype BEFORE the wire — the operand itself is bf16, so
        # no finding even though a widening convert exists upstream
        def step(x):
            acc = (x.astype(jnp.float32) * 2.0).astype(x.dtype)
            return lax.ppermute(acc, "pod", PERM)
        a = audit_jaxpr(jaxpr_of(step, aval(8, 8, dtype=jnp.bfloat16)))
        assert a.ok, [f.format() for f in a.findings]

    def test_off_pod_axis_collective_is_ja403(self):
        def step(x):
            return lax.psum(x, "data")
        a = audit_jaxpr(jaxpr_of(step, aval(8),
                                 axis_env=[("pod", 2), ("data", 2)]))
        assert [f.rule for f in a.findings] == ["JA403"]
        assert "'data'" in a.findings[0].message

    def test_large_closed_constant_is_ja404(self):
        big = np.ones((64, 64), np.float32)          # 16 KiB

        def step(x):
            return x @ jnp.asarray(big)
        a = audit_jaxpr(jaxpr_of(step, aval(8, 64)),
                        const_threshold_bytes=1024)
        assert [f.rule for f in a.findings] == ["JA404"]
        assert a.max_const_bytes == big.nbytes
        # the same const under the default 1 MiB threshold is fine
        assert audit_jaxpr(jaxpr_of(step, aval(8, 64))).ok

    def test_large_numpy_constant_is_ja404(self):
        # a numpy array closed over as-is: the trace keeps it among the
        # ClosedJaxpr's consts (as a TypedNdArray, which has no nbytes)
        big = np.ones((64, 64), np.float32)

        def step(x):
            return x + big
        a = audit_jaxpr(jaxpr_of(step, aval(64, 64)),
                        const_threshold_bytes=1024)
        assert [f.rule for f in a.findings] == ["JA404"]
        assert a.max_const_bytes == big.nbytes

    def test_const_seed_rng_is_ja405_exactly_once(self):
        # PRNGKey(0) baked into the trace: the step replays the same
        # stream every call.  The whole seed->wrap->sample chain must
        # collapse to ONE finding at the root, not one per RNG prim.
        def step(x):
            return x + jax.random.normal(jax.random.PRNGKey(0), x.shape)
        a = audit_jaxpr(jaxpr_of(step, aval(4)))
        assert [f.rule for f in a.findings] == ["JA405"]
        assert a.n_rng_prims >= 1

    def test_key_threaded_through_args_is_clean(self):
        def step(x, key):
            return x + jax.random.normal(key, x.shape)
        a = audit_jaxpr(jaxpr_of(step, aval(4),
                                 aval(2, dtype=jnp.uint32)))
        assert a.ok, [f.format() for f in a.findings]

    @pytest.mark.slow
    def test_broken_combo_is_ja400_row(self):
        # own process: audit_combos builds the 8-device forced-host
        # mesh, so jax must not have been initialized by another test
        code = textwrap.dedent("""
            from repro.analysis import audit_combos
            rows = audit_combos(
                combos=[("train_4k", "dpsgd", "not-a-topology")])
            (combo, a), = rows
            assert combo == "train_4k/dpsgd/not-a-topology", combo
            assert a.error is not None
            assert [f.rule for f in a.findings] == ["JA400"], a.findings
            print("JA400_ROW_OK")
        """)
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=dict(os.environ,
                     PYTHONPATH=os.path.join(REPO_ROOT, "src")),
            cwd=REPO_ROOT, timeout=300)
        assert "JA400_ROW_OK" in r.stdout, r.stdout + r.stderr

    def test_to_json_shape(self):
        def step(x):
            return lax.ppermute(x, "pod", PERM)
        j = audit_jaxpr(jaxpr_of(step, aval(4, 4))).to_json()
        assert j["ok"] and j["n_collectives"] == 1
        assert set(j) >= {"tag", "findings", "collective_axes",
                          "max_const_bytes", "n_rng_prims", "error"}


# ------------------------------------------------------------- the repo

class TestRepoIsClean:
    def test_ast_lints_clean(self):
        assert [f.format() for f in astlint.lint_paths(REPO_ROOT)] == []

    def test_registry_parity_clean(self):
        assert [f.format() for f in check_parity(REPO_ROOT)] == []

    def test_rule_ids_unique_across_passes(self):
        # RA100-104, PA301-305, GA201-205, JA400-405
        assert len(ALL_RULES) == 5 + 5 + 5 + 6

    @pytest.mark.slow
    def test_jaxpr_sweep_covers_matrix_and_is_clean(self):
        # own process: the sweep traces on the 8-device forced-host
        # mesh (launch-test convention — see launch_gossip_script.py)
        code = textwrap.dedent("""
            from repro.analysis import audit_combos
            rows = audit_combos()
            combos = [c for c, _ in rows]
            assert len(combos) == len(set(combos)) == 22, combos
            assert "prefill_32k/-/-" in combos
            assert "decode_32k/-/-" in combos
            assert "train_4k/adpsgd/tv-dcliques" in combos
            bad = [(c, a.error or [f.format() for f in a.findings])
                   for c, a in rows if not a.ok]
            assert bad == [], bad
            print("JAXPR_SWEEP_CLEAN_OK")
        """)
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=dict(os.environ,
                     PYTHONPATH=os.path.join(REPO_ROOT, "src")),
            cwd=REPO_ROOT, timeout=300)
        assert "JAXPR_SWEEP_CLEAN_OK" in r.stdout, r.stdout + r.stderr

    @pytest.mark.slow
    def test_cli_skip_graph_exits_zero(self, tmp_path):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO_ROOT, "src"))
        out = tmp_path / "AUDIT.json"
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--skip-graph",
             "--json", str(out)],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        assert payload["ok"] and payload["counts"]["ast"] == 0

    @pytest.mark.slow
    def test_cli_graph_hlo_end_to_end(self, tmp_path):
        """Crafted HLO in -> exit code + AUDIT.json schema out, then
        the same violation grandfathered via the baseline."""
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO_ROOT, "src"))
        hlo = tmp_path / "step.hlo"
        hlo.write_text(planted_hlo(dtype="f32", out_dtype="f32",
                                   alias=False))
        out = tmp_path / "AUDIT.json"
        bl = tmp_path / "baseline.json"
        cmd = [sys.executable, "-m", "repro.analysis",
               "--graph-hlo", str(hlo), "--devices-per-pod", "2",
               "--json", str(out), "--baseline", str(bl)]
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=REPO_ROOT, timeout=180)
        assert r.returncode == 1, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        assert not payload["ok"]
        assert payload["counts"]["graph"] == 1
        assert payload["counts"]["jaxpr"] == 0   # --graph-hlo: no sweep
        assert payload["counts"]["baselined"] == 0
        assert [f["rule"] for f in payload["findings"]] == ["GA202"]
        assert payload["graph"]["findings"], "graph block carries them"
        assert set(payload["rules"]) == set(ALL_RULES)
        # grandfather the finding, rerun: baselined semantics, exit 0
        r2 = subprocess.run(cmd + ["--update-baseline"],
                            capture_output=True, text=True, env=env,
                            cwd=REPO_ROOT, timeout=180)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        r3 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                            cwd=REPO_ROOT, timeout=180)
        assert r3.returncode == 0, r3.stdout + r3.stderr
        payload3 = json.loads(out.read_text())
        assert payload3["ok"] and payload3["counts"]["baselined"] == 1
        assert payload3["findings"][0]["baselined"]

    @pytest.mark.slow
    def test_cli_default_gate_clean_with_coverage(self, tmp_path):
        """The full default gate (AST + parity + jaxpr sweep + smoke
        compile) is clean on the repo and writes the coverage matrix."""
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO_ROOT, "src"))
        out = tmp_path / "AUDIT.json"
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "-q",
             "--json", str(out)],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=420)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        assert payload["ok"] and payload["stale_baseline"] == []
        cov = payload["coverage"]
        assert len(cov) == 22
        smoke = [row for row in cov
                 if row["combo"] == "train_4k/dpsgd/ring"]
        assert smoke and smoke[0]["hlo"] is not None
        assert smoke[0]["hlo"]["ok"] and "GA201" in smoke[0]["hlo"]["rules"]
        assert all(row["jaxpr"]["ok"] for row in cov)

    @pytest.mark.slow
    def test_cli_fail_on_stale(self, tmp_path):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO_ROOT, "src"))
        bl = tmp_path / "baseline.json"
        bl.write_text('["XX999|nowhere.py|long gone line"]\n')
        out = tmp_path / "AUDIT.json"
        cmd = [sys.executable, "-m", "repro.analysis", "--skip-graph",
               "--json", str(out), "--baseline", str(bl)]
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=REPO_ROOT, timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr   # stale = warn
        assert json.loads(out.read_text())["stale_baseline"] == \
            ["XX999|nowhere.py|long gone line"]
        r2 = subprocess.run(cmd + ["--fail-on-stale"], capture_output=True,
                            text=True, env=env, cwd=REPO_ROOT, timeout=180)
        assert r2.returncode == 1, r2.stdout + r2.stderr
        assert "stale" in r2.stdout
