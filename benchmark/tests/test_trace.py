"""The trace reduction, on small hand-made traces and on a real one."""

import json
import os

import pytest

from benchmark.trace import reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def small():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        return reduce.Trace.from_json(json.load(f))


def test_busy_union_clips_to_window_and_merges_overlaps(small):
    # [10.0, 10.2) + [10.3, 10.45) + [10.8, 11.0) = 0.2 + 0.15 + 0.2
    assert reduce.busy_seconds(small) == pytest.approx(0.55)
    assert reduce.idle_share(small) == pytest.approx(0.45)


def test_program_seconds_sum_by_name_inside_window(small):
    progs = reduce.program_seconds(small)
    assert progs["jit_ssm_block_stage"] == pytest.approx(0.2 + 0.2)
    assert progs["jit_rounds_span_stage"] == pytest.approx(0.15)
    assert progs["jit_copy"] == pytest.approx(0.05)


def test_matching_seconds_reads_the_data_file(small, tmp_path):
    names = tmp_path / "programs.json"
    names.write_text(json.dumps({"programs": ["jit_ssm_block_stage"]}))
    assert reduce.matching_seconds(small, str(names)) == pytest.approx(0.4)


def test_idle_gaps_named_by_host_span(small):
    gaps = reduce.idle_gaps(small)
    # [10.45, 10.8): middle 10.625 falls in "wait"; [10.2, 10.3) in "ingest"
    assert gaps[0][0] == "wait" and gaps[0][1] == pytest.approx(0.35)
    assert gaps[1][0] == "ingest" and gaps[1][1] == pytest.approx(0.1)
    assert len(gaps) == 2
    b = reduce.breakdown(small)
    assert b["device_ops"][0][0] == "jit_ssm_block_stage"
    assert b["idle_gaps"][0][0] == "wait"


@pytest.fixture
def late():
    """A window whose first seconds show no program: calls at 0, 2, 4, 6
    and 8 s, the first program at 3 s, none in the call at 6 s, and one
    after the window closed."""
    calls = [(float(t), t + 2.0, "ingest") for t in range(0, 10, 2)]
    return reduce.Trace(
        [[(3.0, 3.5, "jit_a(1)"), (4.2, 5.0, "jit_ssm_block_stage(2)"),
          (8.1, 9.9, "jit_ssm_block_stage(2)"), (10.5, 11.0, "jit_a(1)")]],
        [(0.0, 10.0, "window")] + calls)


def test_steady_window_opens_with_the_first_call_after_a_program(late):
    # the call at 2 s was running when the first program showed
    assert late.first_call() == 2
    assert late.window() == (4.0, 10.0)
    assert reduce.busy_seconds(late) == pytest.approx(0.8 + 1.8)
    assert reduce.idle_share(late) == pytest.approx(1 - 2.6 / 6.0)
    assert reduce.program_seconds(late) == {
        "jit_ssm_block_stage": pytest.approx(2.6)}


def test_events_ordered_are_counted_over_the_same_calls(late):
    assert reduce.ordered_in_window(late, [10, 20, 30, 40, 50]) == 120
    # the counts are not those of the calls traced
    assert reduce.ordered_in_window(late, [10, 20, 30, 40]) is None


def test_coverage_reports_what_the_trace_missed(late):
    assert reduce.coverage(late) == {
        "left_out_s": 4.0, "calls": 3, "calls_without_program": 1,
        "programs_after_close": 1}


def test_no_device_reads_nothing():
    t = reduce.Trace([], [(0.0, 1.0, "window")])
    assert reduce.idle_share(t) is None


def test_load_reads_benchmark_spans_from_a_real_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("ingest"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("not_ours"):
                pass
    trace = reduce.load(str(tmp_path))
    names = [n for _, _, n in trace.host]
    assert names.count("window") == 1 and names.count("ingest") == 1
    assert "not_ours" not in names
    lo, hi = trace.opened()
    assert hi > lo
