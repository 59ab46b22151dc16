"""The trace reduction on a small trace recorded on a TPU v5e by
``record_fixture.py`` (committed beside this file), and its interval
arithmetic on made-up events."""

import os

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fixture.xplane.pb")


def test_program_name_strips_jit_and_module_id():
    assert tr.program_name("jit_fused_tile_step(123)") == "fused_tile_step"
    assert tr.program_name("jit_screen_step") == "screen_step"


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_label_is_innermost_open_span():
    spans = [(0, 100, "bench.dedup_reps"), (40, 60, "bench.rerank_hook")]
    assert tr._label(spans, 50) == "bench.rerank_hook"
    assert tr._label(spans, 10) == "bench.dedup_reps"
    assert tr._label(spans, 150) == "other_host"


@pytest.fixture(scope="module")
def red():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded TPU trace committed")
    return tr.reduce_trace(FIXTURE)


def test_fixture_device_programs(red):
    assert red.devices == 1
    assert set(red.program_s) >= {"fused_tile_step", "screen_step"}
    assert red.seconds_of("fused_tile_step") > 0


def test_fixture_busy_and_idle(red):
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    # device programs never add up to more than the busy time they make
    assert sum(red.program_s.values()) <= red.busy_s * 1.0001


def test_fixture_breakdown_labels_gaps_by_spans(red):
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    labels = {g[0] for g in b["idle_gaps"]}
    # the 50 ms host sleep inside the dedup span is the longest gap, the
    # 30 ms one after the match span the next
    (l0, s0), (l1, s1) = b["idle_gaps"][:2]
    assert l0 == "bench.dedup_reps" and 0.045 < s0 < 0.06
    assert l1 == "other_host" and 0.025 < s1 < 0.04
    assert labels <= {"bench.dedup_reps", "bench.match_chunk", "other_host"}
