"""The program-span reduction on a small recorded trace (no chip needed)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import span_reduce  # noqa: E402


def _key(field, wire):
    return bytes([field << 3 | wire])


def _varint(n: int) -> bytes:
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _len(field, payload: bytes) -> bytes:
    return _key(field, 2) + _varint(len(payload)) + payload


def _hlo(op_names):
    """An ``xla.HloProto`` whose one computation holds the instructions
    ``name → op_name`` (name=1, metadata=7 → op_name=2)."""
    ins = b"".join(_len(2, _len(1, n.encode()) + _key(2, 0) + b"\x01"
                        + _len(7, _len(1, b"mul") + _len(2, o.encode())))
                   for n, o in op_names.items())
    return _len(1, _len(1, b"jit_fused_stream_step") + _len(3, ins))


def _escaped(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


HLO = {"fusion.1": "jit(fused_stream_step)/layer0/messages/mul",
       "scatter.2": "jit(fused_stream_step)/layer0/scatter/scatter-add",
       "fusion.3": "jit(fused_stream_step)/jit(main)/layer1/update/add"}

# Times in picoseconds from each line's timestamp; the window is [0, 100]
# us.  The chip runs one jit_fused_stream_step program over [5, 95]:
# fusion.1 [10, 30], scatter.2 [40, 50] and fusion.3 [80, 90] take their
# scope from the module's HLO metadata, and copy.4 [92, 94] has none; an
# op of another program runs at [96, 97].
# Idle gaps: [0, 10], [30, 40], [50, 80], [90, 92], [94, 96], [97, 100].
# The host applies one batch over [0, 100]: repro/graph [0, 8],
# repro/exec [33, 100] holding repro/undo_capture [45, 82] and a compile
# [88, 100].
TRACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }}
    events {{ metadata_id: 2 offset_ps: 40000000 duration_ps: 10000000 }}
    events {{ metadata_id: 3 offset_ps: 80000000 duration_ps: 10000000 }}
    events {{ metadata_id: 4 offset_ps: 92000000 duration_ps: 2000000 }}
    events {{ metadata_id: 7 offset_ps: 96000000 duration_ps: 1000000 }}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events {{ metadata_id: 5 offset_ps: 5000000 duration_ps: 90000000 }}
    events {{ metadata_id: 6 offset_ps: 95500000 duration_ps: 2000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "scatter.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "copy.4" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_fused_stream_step(77)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit__take(78)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "fusion.1" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
    timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 8000000 }}
    events {{ metadata_id: 4 offset_ps: 33000000 duration_ps: 67000000 }}
    events {{ metadata_id: 5 offset_ps: 45000000 duration_ps: 37000000 }}
    events {{ metadata_id: 6 offset_ps: 88000000 duration_ps: 12000000 }}
    events {{ metadata_id: 7 offset_ps: 50000000 duration_ps: 1000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "chipbench/window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "chipbench/apply_batch" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "repro/graph" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "repro/exec" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "repro/undo_capture" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "backend_compile_and_load" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "PjitFunction(_take)" }} }}
}}
planes {{
  id: 3
  name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_fused_stream_step(77)"
    stats {{ metadata_id: 1 bytes_value: "{_escaped(_hlo(HLO))}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit__take(78)"
    stats {{ metadata_id: 1 bytes_value: "{_escaped(_hlo({"fusion.1": "layer9/x"}))}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
"""


@pytest.fixture(scope="module")
def xspace():
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(TRACE)


@pytest.fixture(scope="module")
def reduced(xspace):
    from jax.profiler import ProfileData

    return span_reduce.reduce_trace(ProfileData.from_serialized_xspace(xspace),
                                    span_reduce.module_op_names(xspace))


def test_op_names_come_from_the_step_modules_hlo_metadata(xspace):
    assert span_reduce.module_op_names(xspace) == {
        "jit_fused_stream_step(77)": HLO}


def test_idle_gaps_are_labelled_by_the_innermost_program_span(reduced):
    us = pytest.approx
    assert reduced["idle_gaps"] == [
        ["repro/undo_capture", us(30e-6)],  # inside exec, in apply_batch
        ["repro/graph", us(10e-6)],  # graph covers 8 of its 10 us
        ["repro/exec", us(10e-6)],  # exec covers 7 of 10, apply_batch 3
        ["compile", us(3e-6)],  # [97, 100] under the compile annotation
        ["compile", us(2e-6)],
        ["compile", us(2e-6)],
    ]
    assert reduced["idle_by_span"] == {
        "repro/undo_capture": us(30e-6), "repro/graph": us(10e-6),
        "repro/exec": us(10e-6), "compile": us(7e-6)}


def test_step_device_time_is_summed_per_layer_and_stage(reduced):
    us = pytest.approx
    assert reduced["step_runs"] == 1
    assert reduced["scope_device_s"] == {
        "layer0/messages": us(20e-6), "layer0/scatter": us(10e-6),
        "layer1/update": us(10e-6), "unscoped": us(2e-6)}
    assert reduced["step_device_s"] == us(42e-6)  # the other program's op: out
    assert reduced["scoped_share"] == us(40 / 42)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(fused_stream_step)/layer2/delta_agg/gather", "layer2/delta_agg"),
    ("jit(f)/jit(main)/layer10/constrained/dot_general", "layer10/constrained"),
    ("jit(fused_stream_step)/layer1/dynamic_update_slice", "layer1"),
    ("jit(fused_stream_step)/concatenate", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_an_op_name(op_name, scope):
    assert span_reduce.scope_of(op_name) == scope


def test_innermost_cuts_the_time_line_at_every_span_edge():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 6.0), ("c", 4.0, 5.0),
             ("d", 12.0, 13.0)]
    assert span_reduce.innermost(spans) == [
        ("a", 0.0, 2.0), ("b", 2.0, 4.0), ("c", 4.0, 5.0), ("b", 5.0, 6.0),
        ("a", 6.0, 10.0), ("d", 12.0, 13.0)]


@pytest.mark.parametrize("drop", ["/device:TPU", "chipbench/window"])
def test_no_device_plane_or_no_window_gives_nothing(drop):
    from jax.profiler import ProfileData

    text = TRACE.replace(drop, "/host:other" if drop.startswith("/") else "x")
    assert span_reduce.reduce_trace(ProfileData.from_text_proto(text)) is None


def test_reduce_dir_reads_the_newest_trace_file(tmp_path, xspace):
    assert span_reduce.reduce_dir(tmp_path) is None
    (tmp_path / "run" / "host").mkdir(parents=True)
    (tmp_path / "run" / "host" / "vm.xplane.pb").write_bytes(xspace)
    out = span_reduce.reduce_dir(tmp_path)
    assert out["scope_device_s"]["layer0/messages"] == pytest.approx(20e-6)
