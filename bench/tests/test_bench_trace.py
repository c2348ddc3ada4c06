"""Trace reduction on hand-built traces: busy union, idle share, per-name
kernel time, device gaps under host spans, and reading an XSpace file."""
import _paths  # noqa: F401
import pytest

from bench.lib import trace as tr

OPS = [  # (name, start_ns, end_ns): two overlapping ops, then kernels
    ("fusion.1", 0.0, 40.0),
    ("fusion.2", 30.0, 50.0),
    ("slr_matmul_stacked_pallas.3", 70.0, 90.0),
    ("lowrank_matmul_pallas.4", 95.0, 100.0),
]


def test_union_merges_overlaps():
    assert tr.union(OPS) == [(0.0, 50.0), (70.0, 90.0), (95.0, 100.0)]


def test_busy_and_idle_share_over_window():
    assert tr.busy_ns(OPS, 0.0, 100.0) == 75.0
    assert tr.idle_share(OPS, 0.0, 100.0) == pytest.approx(0.25)
    # clipped to a window that cuts the first interval
    assert tr.busy_ns(OPS, 20.0, 80.0) == 40.0


def test_time_by_name_finds_kernel_by_op_kind():
    assert tr.time_by_name(OPS, "slr_matmul_stacked_pallas") == (20.0, 1)
    assert tr.time_by_name(OPS, "lowrank_matmul_pallas") == (5.0, 1)
    assert tr.time_by_name(OPS, "fusion") == (60.0, 2)
    assert tr.time_by_name(OPS, "absent") == (0.0, 0)


def test_op_name_from_hlo_text():
    text = ("%slr_matmul_stacked_pallas.90 = bf16[16,2048]{1,0} custom-call("
            "s32[4353] %concatenate.149), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(text) == "slr_matmul_stacked_pallas.90"
    assert tr.op_kind("slr_matmul_stacked_pallas.90") == "slr_matmul_stacked_pallas"
    # an op that merely consumes the kernel's output is not the kernel
    copy = "%copy.206 = bf16[8] copy(bf16[8] %slr_matmul_stacked_pallas.51)"
    assert tr.op_kind(tr.op_name(copy)) == "copy"


def test_gaps_and_labels_under_host_spans():
    gaps = tr.gaps(OPS, 0.0, 110.0)
    assert gaps == [(50.0, 70.0), (90.0, 95.0), (100.0, 110.0)]
    spans = [("engine.step", 0.0, 55.0), ("bench.idle", 55.0, 110.0)]
    labelled = tr.label_gaps(gaps, spans, k=2)
    assert labelled == [("bench.idle", 20e-9), ("bench.idle", 10e-9)]
    assert tr.label_gaps([(200.0, 210.0)], spans) == [("none", 10e-9)]


def test_host_gap_is_span_less_busy_time():
    spans = [("engine.step", 0.0, 60.0), ("engine.step", 60.0, 100.0)]
    assert tr.host_gap_ns(OPS, spans) == [10.0, 15.0]


def test_top_ops_ranks_kinds_by_summed_time_without_control_flow():
    ops = OPS + [("fusion.9", 100.0, 130.0), ("while.4", 0.0, 130.0)]
    top = tr.top_ops(ops, k=2)
    assert top == [("fusion", 90e-9), ("slr_matmul_stacked_pallas", 20e-9)]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 4000 }
    events { metadata_id: 2 offset_ps: 6000 duration_ps: 2000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8] fusion(f32[8] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%slr_matmul_stacked_pallas.3 = bf16[8] custom-call()" } }
  event_metadata { key: 3 value { id: 3 name: "jit__decode_fn" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 500 } }
  event_metadata { key: 1 value { id: 1 name: "engine.step" } }
  event_metadata { key: 2 value { id: 2 name: "unrelated" } }
}
"""


def test_load_reads_device_ops_modules_and_host_spans(tmp_path):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = tr.load(str(tmp_path), {"engine.step"})
    assert t.ops[0] == [("fusion.7", 1.0, 5.0),
                        ("slr_matmul_stacked_pallas.3", 6.0, 8.0)]
    assert t.modules[0] == [("jit__decode_fn", 0.0, 9.0)]
    assert t.spans == [("engine.step", 0.0, 10.0)]
