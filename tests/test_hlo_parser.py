"""Direct unit tests for the HLO text parser (repro.analysis.hlo) on
crafted snippets: module/instruction parsing, replica-group decoding
(literal and iota forms), trip-count multiplicities, in-place
dynamic-update-slice byte modeling, and the pod-exchange classifier.
The shim ``repro.launch.hlo_analysis`` must keep re-exporting all of
it for external callers."""
import pytest

from repro.analysis import hlo

MODULE = """\
HloModule crafted

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (t: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %t = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[4,4]{1,0} get-tuple-element(%t), index=1
  %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[4,4]{1,0} all-reduce(%d), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %out = (s32[], f32[4,4]{1,0}) tuple(%i, %ar)
}

%cond (t: (s32[], f32[4,4])) -> pred[] {
  %t = (s32[], f32[4,4]{1,0}) parameter(0)
  ROOT %p = pred[] constant(true)
}

ENTRY %main (p0: f32[4,4]) -> (s32[], f32[4,4]) {
  %p0 = f32[4,4]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %init = (s32[], f32[4,4]{1,0}) tuple(%c0, %p0)
  ROOT %w = (s32[], f32[4,4]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
}
"""


class TestParseModule:
    def test_computations_and_entry(self):
        comps = hlo.parse_module(MODULE)
        assert set(comps) == {"add", "body", "cond", "main"}
        assert comps["main"].is_entry
        assert not comps["body"].is_entry

    def test_instruction_fields(self):
        comps = hlo.parse_module(MODULE)
        d = next(i for i in comps["body"].instrs if i.name == "d")
        assert d.op == "dot"
        assert d.type_str == "f32[4,4]{1,0}"
        assert "lhs_contracting_dims={1}" in d.rest
        assert not d.is_root

    def test_root_flag_and_tuple_types(self):
        comps = hlo.parse_module(MODULE)
        root = next(i for i in comps["main"].instrs if i.is_root)
        assert root.name == "w" and root.op == "while"
        assert root.type_str.startswith("(s32[]")

    def test_shape_bytes(self):
        assert hlo._shape_bytes("f32[4,4]{1,0}") == 64
        assert hlo._shape_bytes("(s32[], f32[4,4]{1,0})") == 68
        assert hlo._shape_bytes("bf16[8]") == 16
        assert hlo._shape_bytes("token[]") == 0


class TestMultiplicities:
    def test_while_trip_count_composes(self):
        mult = hlo._multiplicities(hlo.parse_module(MODULE))
        assert mult["main"] == 1.0
        assert mult["body"] == 12.0
        # to_apply callee inherits the body's multiplicity
        assert mult["add"] == 12.0
        # condition computations are deliberately not costed
        assert "cond" not in mult

    def test_uncalled_computation_has_no_multiplicity(self):
        text = MODULE.replace(
            ", to_apply=%add", "").replace("to_apply=%add", "")
        mult = hlo._multiplicities(hlo.parse_module(text))
        assert "add" not in mult


class TestReplicaGroups:
    def test_literal_form(self):
        g = hlo._parse_replica_groups("replica_groups={{0,1},{2,3}}")
        assert g == [[0, 1], [2, 3]]

    def test_iota_form(self):
        g = hlo._parse_replica_groups("replica_groups=[2,2]<=[4]")
        assert g == [[0, 1], [2, 3]]

    def test_iota_with_transpose(self):
        g = hlo._parse_replica_groups(
            "replica_groups=[2,2]<=[2,2]T(1,0)")
        assert g == [[0, 2], [1, 3]]

    def test_absent_means_all_devices(self):
        assert hlo._parse_replica_groups("channel_id=1") == []

    def test_present_but_unparseable_is_none(self):
        assert hlo._parse_replica_groups(
            "replica_groups=<weird v3 form>") is None

    def test_pairs(self):
        p = hlo._parse_pairs("source_target_pairs={{0,1},{1,0}}")
        assert p == [(0, 1), (1, 0)]
        assert hlo._parse_pairs("replica_groups={{0,1}}") is None


class TestDusUpdateBytes:
    def test_bare_dus_counts_update_twice(self):
        text = """\
ENTRY %main (p0: f32[128,16], u: f32[1,16]) -> f32[128,16] {
  %p0 = f32[128,16]{1,0} parameter(0)
  %u = f32[1,16]{1,0} parameter(1)
  %z = s32[] constant(0)
  ROOT %dus = f32[128,16]{1,0} dynamic-update-slice(%p0, %u, %z, %z)
}
"""
        comps = hlo.parse_module(text)
        ent = comps["main"]
        symtab = {i.name: i.type_str for i in ent.instrs}
        dus = next(i for i in ent.instrs if i.op == "dynamic-update-slice")
        # modeled in-place traffic: 2x the 1x16 f32 update = 128 bytes,
        # NOT 2x the 128x16 buffer
        assert hlo._dus_update_bytes(dus, comps, symtab) == 128.0

    def test_non_dus_is_none(self):
        comps = hlo.parse_module(MODULE)
        ent = comps["main"]
        symtab = {i.name: i.type_str for i in ent.instrs}
        w = next(i for i in ent.instrs if i.op == "while")
        assert hlo._dus_update_bytes(w, comps, symtab) is None


class TestAnalyze:
    def test_dot_flops_trip_multiplied(self):
        cost = hlo.analyze(MODULE)
        # dot: 2 * 16 out elems * k=4 contraction = 128 flops x 12 trips
        assert cost.flops == 12 * 128

    def test_collective_bytes_trip_multiplied(self):
        cost = hlo.analyze(MODULE)
        assert cost.collective_bytes["all-reduce"] == 12 * 64
        assert cost.coll_total == 12 * 64


POD_HLO = """\
ENTRY %main (p0: bf16[32]) -> bf16[32] {
  %p0 = bf16[32]{0} parameter(0)
  %cp = bf16[32]{0} collective-permute(%p0), source_target_pairs={{0,2},{2,0},{1,3},{3,1}}
  %lp = bf16[32]{0} collective-permute(%cp), source_target_pairs={{0,1},{1,0}}
  %ar = bf16[32]{0} all-reduce(%lp), replica_groups={{0,1},{2,3}}
  ROOT %ag = bf16[32]{0} all-gather(%ar), replica_groups={{0,2},{1,3}}, dimensions={0}
}
"""


class TestPodExchange:
    def test_classification(self):
        rep = hlo.pod_exchange_report(POD_HLO, 2)
        assert rep.permute_cross_bytes == 64.0   # 0<->2, 1<->3
        assert rep.permute_local_bytes == 64.0   # 0<->1 inside pod 0
        assert rep.reduce_local_bytes == 64.0    # groups {0,1},{2,3}
        assert rep.reduce_cross_bytes == 64.0    # groups {0,2},{1,3}
        assert rep.pod_axis_only
        assert rep.unparsed == 0
        assert rep.cross_pod_bytes == 128.0

    def test_off_axis_pair_flips_pod_axis_only(self):
        text = POD_HLO.replace("{{0,2},{2,0},{1,3},{3,1}}",
                               "{{0,3},{3,0}}")
        rep = hlo.pod_exchange_report(text, 2)
        assert not rep.pod_axis_only

    def test_unparseable_groups_count_cross_and_unparsed(self):
        text = POD_HLO.replace("replica_groups={{0,1},{2,3}}",
                               "replica_groups=<v3>")
        rep = hlo.pod_exchange_report(text, 2)
        assert rep.unparsed == 1
        assert rep.reduce_cross_bytes == 128.0   # conservative bucket

    @pytest.mark.parametrize("operand,want", [
        ("bf16[32]{0}", 64.0),
        ("(bf16[32]{0}, f32[4,2]{1,0})", 96.0),  # combined permute
    ])
    def test_async_permute_counts_operand_once(self, operand, want):
        # TPU's async permute is typed (operand, result, u32[], u32[]):
        # the wire carries the operand once, not operand + result
        text = (
            "ENTRY %main (p0: bf16[32]) -> bf16[32] {\n"
            "  %p0 = bf16[32]{0} parameter(0)\n"
            f"  %cps = ({operand}, {operand}, u32[]{{:S(2)}}, "
            "u32[]{:S(2)}) collective-permute-start(%p0), "
            "source_target_pairs={{0,2},{2,0}}\n"
            f"  ROOT %cpd = {operand} collective-permute-done(%cps)\n"
            "}\n")
        rep = hlo.pod_exchange_report(text, 2)
        assert rep.permute_cross_bytes == want
        assert rep.pod_axis_only and rep.unparsed == 0
        cost = hlo.analyze(text)
        assert cost.collective_bytes["collective-permute"] == want


class TestLaunchShim:
    def test_reexports(self):
        from repro.launch import hlo_analysis as shim
        for name in ("parse_module", "analyze", "pod_exchange_report",
                     "PodExchange", "HLOCost", "COLLECTIVES",
                     "_parse_replica_groups", "_dus_update_bytes"):
            assert getattr(shim, name) is getattr(hlo, name), name
