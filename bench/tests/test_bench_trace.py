"""The trace reduction: on hand-made events, and on a small trace
recorded on the chip (``bench/testdata/record.py``)."""
import json
from pathlib import Path

import pytest

from bench import xplane

DATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1_000_000


def test_busy_union_ops_and_gap_attribution_on_hand_made_events():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.read", 0, 30 * MS),
             ("bench.step", 30 * MS, 90 * MS),
             ("bench.loss_sync", 90 * MS, 100 * MS),
             ("other.span", 0, 100 * MS)]
    dev0 = [("while.1", 35 * MS, 70 * MS),
            ("fusion.2", 50 * MS, 60 * MS),      # inside the while loop
            ("ssd_kernel", 75 * MS, 85 * MS),
            ("fusion.1", -5 * MS, 5 * MS)]       # starts before the window
    dev1 = [("fusion.1", 30 * MS, 100 * MS)]
    got = xplane.reduce_events(spans, [dev0, dev1])
    assert got["window_s"] == pytest.approx(0.1)
    # device 0 busy 0-5, 35-70, 75-85 = 50 ms; device 1 70 ms
    assert got["busy_s"] == pytest.approx(0.06)
    assert got["ops"]["fusion.1"] == pytest.approx((5 + 70) / 2 / 1e3)
    assert got["op_counts"]["fusion.1"] == pytest.approx(1.0)
    # a loop's own time leaves out the operations inside it
    assert got["ops"]["while.1"] == pytest.approx((35 - 10) / 2 / 1e3)
    assert got["ops"]["fusion.2"] == pytest.approx(10 / 2 / 1e3)
    assert got["ops"]["ssd_kernel"] == pytest.approx(0.005)
    # device 0 idle: 5-30 read (25), 30-35 step (5), 70-75 step (5),
    # 85-90 step (5), 90-100 loss sync (10)
    assert got["gaps"] == {"bench.read": pytest.approx(0.025),
                           "bench.step": pytest.approx(0.015),
                           "bench.loss_sync": pytest.approx(0.010)}


def test_op_label_keeps_name_opcode_shape_and_custom_call_target():
    text = ("%closed_call.31 = (f32[8,8,32,256,64]{4,3,2,1,0:T(8,128)}, "
            "f32[8,8,32,128,64]{4,3,2,1,0:T(8,128)}) custom-call(f32[8]{0} "
            "%x), custom_call_target=\"tpu_custom_call\", backend_config={}")
    assert xplane.op_label(text) == (
        "closed_call.31 custom-call (f32[8,8,32,256,64], f32[8,8,32,128,64])"
        " tpu_custom_call")
    assert xplane.op_label("%fusion.3 = bf16[4,128]{1,0} fusion(%a)") == \
        "fusion.3 fusion bf16[4,128]"
    assert xplane.op_label("bench.read") == "bench.read"


def test_no_window_or_no_device_reduces_to_nothing():
    assert xplane.reduce_events([("bench.read", 0, 1)], [[]]) is None
    assert xplane.reduce_events([("bench.window", 0, 1)], []) is None


def test_recorded_chip_trace_reduces_to_the_recorded_numbers():
    want = json.loads((DATA / "mamba2_2layer.reduced.json").read_text())
    got = xplane.reduce_trace(str(DATA / "mamba2_2layer.xplane.pb.xz"))
    assert got["n_devices"] == want["n_devices"] == 1
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["ops"] == pytest.approx(want["ops"], rel=1e-9)
    assert got["gaps"] == pytest.approx(want["gaps"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["busy_s"] + sum(got["gaps"].values()) == pytest.approx(
        got["window_s"], rel=1e-6)
    # the window's idle time falls inside the driver's own spans
    assert set(got["gaps"]) <= {"bench.read", "bench.device_put",
                                "bench.step", "bench.loss_sync", "none"}


def test_ssd_roofline_reads_the_recorded_trace_below_the_peak():
    from types import SimpleNamespace
    from bench import run
    from bench.peaks import peaks
    from bench.tests.conftest import REPO
    reduced = json.loads((DATA / "mamba2_2layer.reduced.json").read_text())
    cell = json.loads((REPO / "bench/workloads/mamba2-370m.train.resident"
                       ".json").read_text())
    cj = json.loads((REPO / "bench/configs/mamba2-370m.json").read_text())
    ctx = SimpleNamespace(trace=reduced, cell=cell, cj=cj, chips=1,
                          peaks=peaks("TPU v5 lite"))
    share = run.metric_reader("ssd_roofline.train")(ctx)
    # two layers, a forward and a recomputed forward each, per step
    assert 0 < share <= 100
    assert sum(n for k, n in reduced["op_counts"].items()
               if k.endswith("(f32[8,8,32,256,64], f32[8,8,32,128,64]) "
                             "tpu_custom_call")) % 4 == 0
