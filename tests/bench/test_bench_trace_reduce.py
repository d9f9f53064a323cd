"""The benchmark's trace reduction (``bench/trace_reduce.py``): busy and
idle union, top device programs, and idle gaps labelled by the harness's
host spans, on hand-made intervals and on a small recorded trace.

``data/cpu_window.xplane.pb`` was recorded on the CPU with
``jax.profiler``: inside one ``window`` span, three rounds of ``submit``
(2 ms sleep), ``step`` (one jitted sine and matmul, two device operations),
``poll`` (1 ms sleep) and ``wait`` (20 ms sleep).
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce as T  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "cpu_window.xplane.pb")


@pytest.fixture
def hand():
    return T.Trace(
        device_ops=[[("a", 0, 10), ("b", 5, 20), ("a", 30, 40)]],
        spans=[("window", 0, 50), ("step", 0, 22), ("wait", 22, 31),
               ("poll", 40, 50)])


def test_union_and_busy_on_hand_intervals(hand):
    assert list(T.Intervals([(5, 20), (0, 10), (30, 40), (35, 36)])) == [
        (0, 20), (30, 40)]
    assert T.busy(hand, 0, 50) == 30
    assert T.busy(hand, 8, 35) == 12 + 5


def test_top_ops_sum_each_program_inside_the_window(hand):
    top = T.top_ops(hand, 0, 50)
    assert [n for n, _ in top] == ["a", "b"]
    assert [v for _, v in top] == pytest.approx([20e-9, 15e-9])
    assert [n for n, _ in T.top_ops(hand, 0, 50, n=1)] == ["a"]


def test_gaps_are_labelled_by_the_span_covering_most_of_them(hand):
    assert T.gaps(hand, 0, 50) == [(20, 30), (40, 50)]
    labels = T.idle_by_label(hand, 0, 50)
    assert sorted(n for n, _ in labels) == ["poll x1", "wait x1"]
    assert [v for _, v in labels] == pytest.approx([10e-9, 10e-9])
    bare = T.Trace(device_ops=hand.device_ops, spans=[("window", 0, 50)])
    (name, value), = T.idle_by_label(bare, 0, 50)
    assert name == f"{T.UNCOVERED} x2" and value == pytest.approx(20e-9)


def test_host_minus_device_per_span(hand):
    assert T.host_minus_device(hand, "step", 0, 50) == [22 - 20]


def test_busy_averages_over_devices():
    tr = T.Trace(device_ops=[[("a", 0, 10)], [("a", 0, 30)]],
                 spans=[("window", 0, 40)])
    assert T.busy(tr, 0, 40) == 20
    (name, value), = T.top_ops(tr, 0, 40)
    assert name == "a" and value == pytest.approx(20e-9)


def _merged_length(ops, lo, hi):
    """Independent union: clip, sort, sweep."""
    total, end = 0.0, lo
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in ops):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_a_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError, match="no /device:TPU: plane"):
        T.load(RECORDED)


def test_recorded_trace_reduces_consistently():
    tr = T.load(RECORDED, host_stand_in=True)
    lo, hi = tr.span("window")
    ops = tr.device_ops[0]
    assert tr.n_devices == 1
    assert len(ops) == 6 and {name for name, _, _ in ops} == {"jit__lambda"}
    assert sorted(n for n, _, _ in tr.spans).count("step") == 3
    busy = T.busy(tr, lo, hi)
    assert busy == pytest.approx(_merged_length(ops, lo, hi))
    assert 0 < busy < hi - lo
    idle = sum(e - s for s, e in T.gaps(tr, lo, hi))
    assert idle + busy == pytest.approx(hi - lo)
    labels = T.idle_by_label(tr, lo, hi)
    assert labels[0][0] == "wait x3"
    (label, after, long), *_ = T.longest_gaps(tr, lo, hi)
    assert label == "wait" and 0.02 <= long < 0.03 and 0 < after < (hi - lo) * 1e-9
    assert sum(v for _, v in labels) == pytest.approx(idle * 1e-9)
    (name, seconds), = T.top_ops(tr, lo, hi)
    assert name == "jit__lambda" and seconds >= busy * 1e-9
    per_step = T.host_minus_device(tr, "step", lo, hi)
    steps = [e - s for n, s, e in tr.spans if n == "step"]
    assert len(per_step) == 3
    assert all(0 <= h < s for h, s in zip(per_step, steps))


def test_find_xplane_picks_the_file(tmp_path):
    sub = tmp_path / "plugins" / "profile" / "run1"
    sub.mkdir(parents=True)
    (sub / "host.xplane.pb").write_bytes(b"")
    assert T.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
    with pytest.raises(FileNotFoundError):
        T.find_xplane(str(tmp_path / "plugins" / "none"))
