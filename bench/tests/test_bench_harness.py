"""The harness end to end on the CPU at a tiny size, its refusal of any
backend but a TPU, and a cell and a metric that arrive as files alone."""
import json
import os
import subprocess
import sys

import pytest

from bench import flops, run
from bench.tests.conftest import REPO, run_tiny


def test_train_driver_runs_a_tiny_cell_through_the_harness(tiny_root):
    out = run_tiny(tiny_root)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "scan_MB_per_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] >= 1 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["bytes_mismatch"] == {"value": 0, "limit": 0}


def test_traced_run_reports_the_per_layer_metrics(tiny_root):
    out = run_tiny(tiny_root, trace=True)
    # the CPU has no TPU plane: the device-trace metrics stay silent
    assert "device_idle_share.train" not in out["metrics"]
    assert "ssd_roofline.train" not in out["metrics"]
    for name in ("mfu.train", "input_wait_share.train", "chr.train",
                 "remote_bytes_per_token.train", "chr.scan"):
        assert out["metrics"][name]["unit"] in ("%", "B/token"), name
    assert out["metrics"]["remote_bytes_per_token.train"]["value"] > 0


def test_a_new_cell_and_metric_are_found_from_files_alone(tiny_root):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cell = json.loads((tiny_root / "bench/workloads/"
                       "tiny.train.oversub-scan.json").read_text())
    cell.pop("tenants")
    (tiny_root / "bench/workloads/tiny.train.alone.json").write_text(
        json.dumps(cell))
    spec["workloads"].append(dict(spec["workloads"][0],
                                  name="tiny.train.alone", traffic="alone"))
    spec["per_layer"].append({
        "name": "steps_counted.train", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "entry", "moves":
        "train_tokens_per_s", "workloads": ["tiny.train.alone"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    (tiny_root / "bench/metrics/steps_counted.train.py").write_text(
        "def read(ctx):\n    return float(ctx.counters['steps'])\n")
    names = [m["name"] for m in run.metrics_for(spec, "tiny.train.alone",
                                                "per_layer")]
    assert names == ["steps_counted.train"]
    out = run_tiny(tiny_root, "tiny.train.alone", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["steps_counted.train"]["value"] >= 1


def test_run_refuses_any_backend_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mamba2-370m.train.oversub-scan", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    from bench.peaks import peaks
    assert peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v99")


@pytest.mark.parametrize("in_itemsize", [2, 4])
def test_ssd_chunk_work_against_a_hand_count(in_itemsize):
    # b=1, s=4, one chunk of 4, h=1, p=2, n=3: X 8, B 12, C 12 elements
    # at the compute dtype; a 4, Y 8, states 6 at float32
    ops, nbytes = flops.ssd_chunk_work(1, 4, 1, 2, 3, chunk=4,
                                       in_itemsize=in_itemsize)
    assert ops == 2 * 16 * 3 + 2 * 16 * 2 + 2 * 4 * 2 * 3
    assert nbytes == in_itemsize * (8 + 12 + 12) + 4 * (4 + 8 + 6)


def test_ssd_kernel_work_at_the_cell_takes_bfloat16_operands():
    from bench.families import mamba2
    cj = json.loads((REPO / "bench/configs/mamba2-370m.json").read_text())
    ops, nbytes = mamba2.ssd_kernel_work(cj, 8, 2048)
    b, s, h, p, n = 8, 2048, 32, 64, 128
    assert nbytes == 2 * (b * s * h * p + 2 * b * s * n) + 4 * (
        b * s * h + b * s * h * p + b * 8 * h * p * n)
    assert ops == b * 8 * (2 * 256 * 256 * n + 2 * 256 * 256 * h * p
                           + 2 * 256 * h * p * n)


def test_mamba2_train_ops_against_a_hand_count():
    cj = {"d_model": 4, "n_layer": 2, "vocab_size": 10,
          "pad_vocab_size_multiple": 8,
          "ssm_cfg": {"expand": 2, "d_state": 2, "headdim": 4,
                      "chunk_size": 2}}
    # d_in 8, 2 heads: in_proj 4 x (16 + 4 + 2) = 88, out_proj 32; the
    # head over the vocabulary padded to 16
    per_layer = 2 * (88 + 32) + (2 * 2 * 2 + 2 * 2 * 8 + 4 * 8 * 2)
    assert flops.mamba2_train_ops_per_token(cj) == 3 * (
        2 * per_layer + 2 * 4 * 16)


def test_full_size_cells_use_the_published_widths():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cj = json.loads((REPO / c["file"]).read_text())
        assert c["source"] == cj["source"]
        assert not any(k.endswith(("_dim", "_rank")) or k == "d_model"
                       for k in c["reduced"])
    cj = json.loads((REPO / "bench/configs/mamba2-370m.json").read_text())
    assert (cj["d_model"], cj["n_layer"], cj["ssm_cfg"]["d_state"],
            cj["ssm_cfg"]["headdim"]) == (1024, 48, 128, 64)
    # the published vocabulary, padding and tied head
    assert (cj["vocab_size"], cj["pad_vocab_size_multiple"],
            cj["tie_embeddings"]) == (50277, 16, True)
