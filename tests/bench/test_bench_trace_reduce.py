"""The trace reduction on a small recorded trace (no chip needed)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace_reduce  # noqa: E402

# Times in picoseconds (offset_ps/duration_ps) from each line's timestamp.
# Window [0, 100 us].  Chip 0 runs fusion.1 [10, 30], scatter.2 [20, 40]
# and copy.3 [60, 70] plus an op cut by the window's end; chip 1 runs one
# op [0, 20].  The host serves reads in [0, 12], applies a batch in
# [40, 95] (graph 5 us, plan 25 us, exec 25 us, laid out to end at 95) and
# sleeps in [95, 100].
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 110000000 duration_ps: 5000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "scatter.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(123456)" } }
}
planes {
  id: 2
  name: "/device:TPU:1"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 55000000 }
    events { metadata_id: 4 offset_ps: 95000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench/window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench/serve_reads" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench/apply_batch" } }
  event_metadata { key: 4 value { id: 4 name: "chipbench/sleep" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(step)" } }
}
"""
SPLITS = [(5e-6, 25e-6, 25e-6)]


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace_reduce.reduce_trace(ProfileData.from_text_proto(TRACE), SPLITS)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(reduced):
    # chip 0: union of [10,40] and [60,70] = 40 us; chip 1: 20 us
    assert reduced["busy_s"] == pytest.approx(30e-6)
    assert reduced["window_s"] == pytest.approx(100e-6)


def test_top_ops_are_summed_by_name_inside_the_window(reduced):
    ops = dict(reduced["device_ops"])
    # named with the program they ran in, where the module line has one
    assert ops["jit_step/fusion.1"] == pytest.approx(20e-6)
    assert ops["fusion.1"] == pytest.approx(20e-6)  # chip 1: no module line
    assert ops["jit_step/scatter.2"] == pytest.approx(20e-6)
    assert ops["jit_step/copy.3"] == pytest.approx(10e-6)
    assert len(ops) == 4  # the module line itself is not an op


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(reduced):
    assert reduced["idle_gaps"] == [
        ["exec", pytest.approx(30e-6)],
        ["plan", pytest.approx(20e-6)],
        ["serve_reads", pytest.approx(10e-6)],
    ]
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(60e-6)


def test_split_lays_phases_end_to_end_ending_with_the_call():
    spans = [("chipbench/apply_batch", 40_000.0, 95_000.0)]
    got = {name: (a, b) for name, a, b in
           trace_reduce.labelled_spans(spans, SPLITS)}
    assert got["exec"] == pytest.approx((70_000.0, 95_000.0))
    assert got["plan"] == pytest.approx((45_000.0, 70_000.0))
    assert got["graph"] == pytest.approx((40_000.0, 45_000.0))


@pytest.mark.parametrize("drop", ["/device:TPU", "chipbench/window"])
def test_no_device_plane_or_no_window_gives_nothing(drop):
    from jax.profiler import ProfileData

    text = TRACE.replace(drop, "/host:other" if drop.startswith("/") else "x")
    assert trace_reduce.reduce_trace(ProfileData.from_text_proto(text),
                                     SPLITS) is None


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
