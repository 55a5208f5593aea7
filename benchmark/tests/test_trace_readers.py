"""The readers PR 25 added, on plain forms a file can hold: the uncovered
time of a profile (`profile_uncovered`), device time by program and by scope
(`trace_named_ms`) and idle time inside host spans (`trace_gap_spans`), the
last two on `recorded_host_trace.json`: one device plane and two host
threads, laid out on round numbers.

    device  |1 bm25 3 sort_key 4.5 topk 6 pack 7|  idle  |8 fill 9|  idle  |12 aggs 14.5 ? 15|   (ms)
    thread 1    execute(readback) 6.5-7.6   mask_fill 8-9.5     root_finalize 11-12.5
    thread 2      dispatch_prepare 7.4-8.1                          execute 11.9-15.1
"""

import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)

import run
import trace_events

profile_uncovered = run.load_module("readers", "profile_uncovered")
trace_named_ms = run.load_module("readers", "trace_named_ms")
trace_gap_spans = run.load_module("readers", "trace_gap_spans")


class RecordedRun:
    """What the readers look at, of a run that never was."""

    def __init__(self, planes=None, records=None):
        self.trace = {"busy_s": 0.010} if planes is not None else None
        self.trace_events = planes
        self.trace_span = (100.0, 104.0)
        self.records = records if records is not None else [
            {"ok": True, "t_done": 101.0}, {"ok": True, "t_done": 103.9},
            {"ok": True, "t_done": 104.5},      # answered after the span
            {"ok": False, "t_done": 102.0}]     # failed inside it


@pytest.fixture(scope="module")
def planes() -> dict:
    with open(os.path.join(TESTS, "recorded_host_trace.json")) as fh:
        return json.load(fh)


def stage(run_, match):
    return trace_named_ms.read(run_, "XLA Ops", match, "query")


def test_device_time_by_scope_sums_to_the_busy_time(planes):
    table = trace_named_ms.by_scope(planes)
    assert table == pytest.approx({
        "bm25_score": 2.0, "sort_key": 1.5, "topk": 1.5, "pack": 1.0,
        "mask_fill": 1.0, "aggs": 2.5, "unscoped": 0.5})
    ops = trace_events.plane_line(planes, "/device:TPU:0", "XLA Ops")
    busy_ns = sum(end - start for start, end in trace_events.intervals_union(
        [(s, s + d) for _, s, d, _ in ops]))
    assert sum(table.values()) == pytest.approx(busy_ns / 1e6)


def test_an_operation_counts_once_under_its_outermost_scope(planes):
    # mask_fill/term_mask/scatter is the fill's, and so is the copy with no
    # path at all that ran inside the fill program; a vmapped program's
    # `vmap(bm25_score)` is bm25_score; a source file named aggs.py is not
    # the aggs scope
    assert trace_events.scope_of(
        {"tf_op": "jit(qw_mask_fill)/jit(main)/mask_fill/term_mask/scatter"}
    ) == "mask_fill"
    assert trace_events.scope_of(
        {"tf_op": "jit(qw_stacked_q4_k10)/jit(main)/vmap(aggs.terms)/add"}
    ) == "aggs"
    assert trace_events.scope_of(
        {"source": "/root/repo/quickwit_tpu/ops/aggs.py:88"}) is None
    assert trace_events.scope_of({"hlo_category": "data formatting"}) is None
    table = trace_named_ms.by_scope(planes)
    assert "term_mask" not in table and table["mask_fill"] == 1.0


def test_stage_ms_is_per_request_answered_inside_the_span(planes):
    run_ = RecordedRun(planes)          # two answered inside the span
    assert stage(run_, "aggs") == pytest.approx(1.25)
    assert stage(run_, ["sort_key", "topk"]) == pytest.approx(1.5)
    assert stage(run_, "unscoped") == pytest.approx(0.25)
    assert stage(run_, "term_mask") == 0.0      # read, and found none
    assert stage(RecordedRun(planes, records=[]), "aggs") is None


def test_program_ms_is_the_median_run_of_a_named_program(planes):
    run_ = RecordedRun(planes)
    assert trace_named_ms.read(run_, "XLA Modules", "jit_qw_",
                               "event") == pytest.approx(3.0)
    assert trace_named_ms.read(run_, "XLA Modules", "jit_qw_mask_fill",
                               "event") == pytest.approx(1.0)


def test_idle_time_inside_host_spans(planes, capsys):
    idle, covered, inside = trace_gap_spans.attribute(planes)
    # 7-8 ms: two threads' spans overlap and cover it all, counted once;
    # 9-12 ms: mask_fill covers 9-9.5, nothing 9.5-11, root_finalize and
    # thread 2's execute 11-12
    assert idle == 4_000_000 and covered == 2_500_000
    assert inside["qw.execute"] == 600_000 + 100_000
    assert inside["qw.dispatch_prepare"] == 600_000
    assert inside["qw.mask_fill"] == 500_000
    assert inside["qw.root_finalize"] == 1_000_000
    assert inside["qw.plan_build"] == 300_000
    assert trace_gap_spans.read(RecordedRun(planes)) == pytest.approx(62.5)
    said = capsys.readouterr().out
    assert said.startswith("[idle] 4.000 ms idle") and "qw.root_finalize" in said


def test_a_program_from_before_the_names_reads_as_nothing(planes):
    """The parent of PR 25: `jit_packed`, no scope in any stat, no `qw.*`
    span. Every new trace metric is left out; none reads 0, none raises."""
    old = {"/device:TPU:0": [
        {"line": "XLA Modules", "events": [
            ["jit_packed(1)", 1000, 5000, {}],
            ["jit_mask_fn(2)", 7000, 1000, {}]]},
        {"line": "XLA Ops", "events": [
            ["%fusion.9 = f32[10000384]{0} fusion(...)", 1000, 5000,
             {"hlo_category": "data formatting", "program_id": 1}],
            ["%scatter = pred[10000384]{0} scatter(...)", 7000, 1000, {}]]}]}
    run_ = RecordedRun(old)
    assert trace_named_ms.by_scope(old) is None
    for match in list(trace_events.SCOPES) + ["unscoped"]:
        assert stage(run_, match) is None
    assert trace_named_ms.read(run_, "XLA Modules", "jit_qw_", "event") is None
    assert trace_gap_spans.read(run_) is None
    untraced = RecordedRun(None)
    assert stage(untraced, "aggs") is None
    assert trace_gap_spans.read(untraced) is None


def phase(name, start, duration, **more):
    return dict(more, name=name, start_ms=start, duration_ms=duration)


def test_uncovered_time_is_wall_less_the_union_of_the_phases():
    profile = {"wall_ms": 100.0, "phases": [
        phase("root_plan", 1.0, 4.0),
        phase("plan_build", 10.0, 5.0),
        # two threads at once: 20-60 and 30-50 cover 40 ms, not 60
        phase("execute", 20.0, 40.0), phase("staging_upload", 30.0, 20.0),
        phase("group_execute_wait", 60.0, 30.0),
        phase("root_merge", 98.0, 5.0)]}        # runs past the wall: cut
    bare, holes = profile_uncovered.uncovered(profile)
    assert holes == [(1.0, "start", "root_plan"),
                     (5.0, "root_plan", "plan_build"),
                     (5.0, "plan_build", "execute"),
                     (8.0, "group_execute_wait", "root_merge")]
    assert bare == pytest.approx(19.0)


def test_a_remote_leafs_phases_come_off_by_their_own_length():
    profile = {"wall_ms": 50.0, "phases": [phase("root_plan", 0.0, 5.0),
                                           phase("root_merge", 45.0, 5.0)],
               "leaves": [{"wall_ms": 38.0, "phases": [
                   phase("plan_build", 0.0, 8.0), phase("execute", 6.0, 24.0)
               ]}]}
    bare, _ = profile_uncovered.uncovered(profile)
    assert bare == pytest.approx(40.0 - 30.0)


def test_the_metric_is_the_median_over_profiled_requests(capsys):
    def request(hole):
        return {"ok": True, "t_done": 101.0, "profile": {
            "wall_ms": 100.0, "phases": [phase("execute", 0.0, 100.0 - hole)]}}
    run_ = RecordedRun(records=[request(2.0), request(30.0), request(4.0),
                                {"ok": True, "t_done": 1.0, "profile": None}])
    assert profile_uncovered.read(run_) == pytest.approx(4.0)
    said = capsys.readouterr().out
    assert "over 3 profiled requests" in said and "('execute', 'end')" in said
    assert profile_uncovered.read(RecordedRun(records=[])) is None


XSPACE = """
planes { id: 9 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 100
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 6000000
      stats { metadata_id: 2 str_value: "q1" } }
    events { metadata_id: 3 offset_ps: 1 duration_ps: 2 } }
  event_metadata { key: 1 value { id: 1 name: "qw.execute" } }
  event_metadata { key: 3 value { id: 3 name: "shard_args" } }
  stat_metadata { key: 2 value { id: 2 name: "query_id" } } }
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 100
    events { metadata_id: 7 offset_ps: 1000000 duration_ps: 2000000
      stats { metadata_id: 3 int64_value: -42 } }
    events { metadata_id: 8 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 9000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 100
    events { metadata_id: 9 offset_ps: 1000000 duration_ps: 10000000 } }
  lines { id: 3 name: "Async XLA Ops"
    events { metadata_id: 8 offset_ps: 1 duration_ps: 2 } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.9 = f32[8]"
      display_name: "fusion.9"
      stats { metadata_id: 4
              str_value: "jit(qw_solo_k10)/vmap(aggs.terms)/scatter-add:" }
      stats { metadata_id: 5 ref_value: 6 }
      stats { metadata_id: 10 double_value: 2.5 }
      stats { metadata_id: 11 uint64_value: 12345678901234 } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.1" } }
  event_metadata { key: 9 value { id: 9 name: "jit_qw_solo_k10(1)" } }
  stat_metadata { key: 3 value { id: 3 name: "run_id" } }
  stat_metadata { key: 4 value { id: 4 name: "tf_op" } }
  stat_metadata { key: 5 value { id: 5 name: "hlo_category" } }
  stat_metadata { key: 6 value { id: 6 name: "data formatting" } }
  stat_metadata { key: 10 value { id: 10 name: "flops" } }
  stat_metadata { key: 11 value { id: 11 name: "bytes_accessed" } } }
"""


def test_a_trace_file_loads_with_the_stats_of_its_events_metadata(tmp_path):
    """`ProfileData` shows an event's own stats only; on the chip the path an
    operation was lowered from is a stat of its metadata record. The file is
    JAX's own serialisation of a text proto, so the wire reader's field
    numbers are held to the real message definitions."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    import xplane_wire
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    records = xplane_wire.event_metadata(str(path), "/device:")
    assert list(records) == ["/device:TPU:0"]
    first = records["/device:TPU:0"]["XLA Ops"][0]
    assert first == {"name": "%fusion.9 = f32[8]", "display_name": "fusion.9",
                     "tf_op": "jit(qw_solo_k10)/vmap(aggs.terms)/scatter-add:",
                     "hlo_category": "data formatting", "flops": 2.5,
                     "bytes_accessed": 12345678901234}
    planes = trace_events.load(str(path))
    ops = trace_events.plane_line(planes, "/device:TPU:0", "XLA Ops")
    assert [(name, start, duration) for name, start, duration, _ in ops] == [
        ("%fusion.9 = f32[8]", 1100, 2000), ("%copy.1", 4100, 2000),
        ("%fusion.9 = f32[8]", 9100, 2000)]
    assert ops[0][3]["run_id"] == -42 and "run_id" not in ops[2][3]
    assert [trace_events.scope_of(e[3]) for e in ops] == ["aggs", None, "aggs"]
    # device planes keep their two lines, host lines their qw.* spans
    assert {e["line"] for e in planes["/device:TPU:0"]} == {"XLA Ops",
                                                            "XLA Modules"}
    assert trace_events.host_spans(planes) == [
        ["qw.execute", 5100, 6000, {"query_id": "q1"}]]
    assert trace_named_ms.by_scope(planes) == pytest.approx(
        {"aggs": 0.004, "unscoped": 0.002})
