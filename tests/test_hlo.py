"""HLO text analysis (``repro.analysis.hlo``) on hand-written modules."""
from repro.analysis import hlo


class TestHLOAnalyzerEdges:
    def test_empty_module(self):
        res = hlo.analyze("HloModule empty\n")
        assert res["flops"] == 0

    def test_unknown_trip_count_flagged(self):
        txt = """HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  ROOT %t = (s32[], f32[4]) tuple(%p)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %c = s32[] constant(0)
  %tup = (s32[], f32[4]) tuple(%c, %a)
  %w = (s32[], f32[4]) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[4] get-tuple-element(%w), index=1
}
"""
        res = hlo.analyze(txt)
        assert res["dynamic_while"] is True

    def test_collective_types_separated(self):
        txt = """HloModule m

ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128] parameter(0)
  %ag = f32[256] all-gather(%a), dimensions={0}
  %ar = f32[128] all-reduce(%a), to_apply=%add
  ROOT %cp = f32[128] collective-permute(%a), source_target_pairs={{0,1}}
}
"""
        res = hlo.analyze(txt)
        cb = res["collective_bytes"]
        assert cb["all-gather"] == 512
        assert cb["all-reduce"] == 512
        assert cb["collective-permute"] == 512
