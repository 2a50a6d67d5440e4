"""The qwen3-1.7b training cell: its configuration at the published
widths, its entries in ``BENCHMARK.json``, the work its metrics are
measured against, its readers on hand-made traces, a tiny copy of it
driven through the harness on one CPU device and on a (4, 1) mesh of
four, and the chip's calibration readings judged by its limits."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import check, run
from bench.families import qwen3
from bench.tests.conftest import REPO

CELL = "qwen3-1.7b.train.fsdp4-oversub"
NAME = "tiny.train.fsdp"
CONFIG = REPO / "bench/configs/qwen3-1.7b.json"
CHIP_READINGS = REPO / "bench/testdata/qwen3-1.7b.calibration.json"


def load(path):
    return json.loads(path.read_text())


def write_qwen3_root(root, chips: int = 1):
    """BENCHMARK.json with the qwen3 cell cut to a size the CPU runs in
    seconds (every width divided, 2 layers, 4 rows of 64 tokens), its
    cell file and its configuration, under ``root``."""
    spec = load(REPO / "BENCHMARK.json")
    cell = load(REPO / "bench/workloads" / f"{CELL}.json")
    cj = load(CONFIG)
    cj.update(name="tiny-qwen3", hidden_size=64, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              intermediate_size=128, vocab_size=256)
    cj["deployment"] = dict(cj["deployment"], chips=chips, rows=4,
                            seq_len=64)
    cell["job"].update(batch=4, seq_len=64)
    cell["corpus"].update(file_size=256 * 1024)
    cell["cache"].update(capacity=1 << 20, min_share=128 << 10,
                         rebalance_quantum=128 << 10)
    cell["link"].update(latency_s=0.002)
    cell["fill"].update(train_batches=20)
    entry = dict(next(w for w in spec["workloads"] if w["name"] == CELL),
                 name=NAME, config=cj["name"], chips=chips)
    spec["workloads"] = [entry]
    spec["configs"] = [dict(next(c for c in spec["configs"]
                                 if c["name"] == "qwen3-1.7b"),
                            name=cj["name"],
                            file=f"bench/configs/{cj['name']}.json")]
    for kind in ("end_to_end", "per_layer"):
        spec[kind] = [dict(m, workloads=[NAME]) if "workloads" in m else m
                      for m in spec[kind]
                      if CELL in m.get("workloads", [CELL])]
    (root / "bench/workloads").mkdir(parents=True, exist_ok=True)
    (root / "bench/configs").mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench/metrics", root / "bench/metrics")
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    (root / "bench/workloads" / f"{NAME}.json").write_text(json.dumps(cell))
    (root / "bench/configs" / f"{cj['name']}.json").write_text(json.dumps(cj))
    return root


def measure_tiny(root, seed: int, trace: bool = False) -> tuple:
    """The harness's result for the tiny cell, and the losses of its
    checked steps."""
    import jax
    from bench import peaks
    from bench.drivers import train
    # the CPU is no device the benchmark measures: a stand-in peak entry
    peaks.PEAKS.setdefault("cpu", {"flops": 1e12, "hbm_Bps": 1e11})
    seen = []
    checked = train.TrainJob.checked_steps

    def spy(self, n):
        seen.append(checked(self, n))
        return seen[-1]

    train.TrainJob.checked_steps = spy
    try:
        spec, entry, cell, cj = run.load_cell(NAME, root)
        out = run.measure(spec, entry, cell, cj, seed, 1.0, trace,
                          jax.devices(), root=root)
    finally:
        train.TrainJob.checked_steps = checked
    return out, seen[0]["losses"]


# ------------------------------------------------------------ the files

def test_the_configuration_keeps_the_published_widths():
    cj = load(CONFIG)
    assert cj["source"] == "https://huggingface.co/Qwen/Qwen3-1.7B"
    assert (cj["num_hidden_layers"], cj["hidden_size"],
            cj["num_attention_heads"], cj["num_key_value_heads"],
            cj["head_dim"], cj["intermediate_size"],
            cj["vocab_size"]) == (28, 2048, 16, 8, 128, 6144, 151936)
    assert (cj["rms_norm_eps"], cj["rope_theta"], cj["tie_word_embeddings"],
            cj["attention_bias"], cj["hidden_act"]) == (
        1e-6, 1000000, True, False, "silu")
    assert cj["param_dtype"] == cj["compute_dtype"] == "bfloat16"
    assert cj["departures"] == []
    cfg = qwen3.model_config(cj)
    # 1.72 B parameters, the embedding tied to the head
    assert 1.70e9 < cfg.param_count() < 1.73e9
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.hd) == (True, True, 128)


def test_the_cell_is_appended_on_four_chips_and_cuts_nothing():
    spec = load(REPO / "BENCHMARK.json")
    assert [c["name"] for c in spec["configs"]][-1] == "qwen3-1.7b"
    assert spec["configs"][-1]["reduced"] == []
    entry = spec["workloads"][-1]
    assert (entry["name"], entry["chips"]) == (CELL, 4)
    assert len(entry["why"]) <= 200
    names = [m["name"] for m in run.metrics_for(spec, CELL, "per_layer")]
    assert set(names) == {"device_idle_share.train", "mfu.train",
                          "input_wait_share.train", "chr.train",
                          "remote_bytes_per_token.train",
                          "flash_attention_roofline.train",
                          "collective_share.train"}
    assert [m["name"] for m in run.metrics_for(spec, CELL, "end_to_end")] \
        == ["train_tokens_per_s", "setup_s"]
    cell = load(REPO / "bench/workloads" / f"{CELL}.json")
    assert "tenants" not in cell
    assert (cell["job"]["batch"], cell["job"]["seq_len"]) == (8, 2048)


def test_parent_benchmark_without_the_cell_exits_at_once(tmp_path):
    """A checkout whose BENCHMARK.json lacks the cell refuses it before
    it looks for a chip."""
    spec = load(REPO / "BENCHMARK.json")
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] != CELL]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(run.BenchError, match="no cell"):
        run.load_cell(CELL, tmp_path)


# ---------------------------------------------------------------- work

def test_train_ops_per_token_against_a_hand_count():
    cj = {"hidden_size": 8, "num_hidden_layers": 2, "vocab_size": 10,
          "num_attention_heads": 2, "num_key_value_heads": 1,
          "head_dim": 4, "intermediate_size": 16,
          "deployment": {"seq_len": 6}}
    # q 8x8, k and v 8x4 each, o 8x8, gate, up and down 8x16 each
    proj = 64 + 2 * 32 + 64 + 3 * 128
    # causal half: QK^T and PV over 3 keys on average, 2 heads of 4
    attn = 2 * 3 * 2 * 4
    assert qwen3.train_ops_per_token(cj) == 3 * (2 * 2 * (proj + attn)
                                                 + 2 * 8 * 10)


def test_train_ops_per_token_at_the_cell():
    ops = qwen3.train_ops_per_token(load(CONFIG))
    assert ops == pytest.approx(1.1027e10, rel=1e-4)


@pytest.mark.parametrize("dtype,itemsize", [("bfloat16", 2), ("float32", 4)])
def test_flash_kernel_work_against_a_hand_count(dtype, itemsize):
    cj = {"num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 8, "compute_dtype": dtype}
    ops, nbytes = qwen3.flash_kernel_work(cj, batch=3, seq_len=10)
    # QK^T and PV over the 50 of 100 (q, k) pairs of the causal half
    assert ops == 3 * 4 * (2 * 50 * 8 + 2 * 50 * 8)
    # q and o: 3 x 10 x 4 heads x 8; k and v: 3 x 10 x 2 heads x 8
    assert nbytes == itemsize * (2 * 960 + 2 * 480)


# ------------------------------------------------------------- readers

def ctx(ops, window_s=2.0):
    from types import SimpleNamespace
    cell = load(REPO / "bench/workloads" / f"{CELL}.json")
    counts = {n: 10.0 for n in ops}
    return SimpleNamespace(
        trace={"ops": ops, "op_counts": counts, "window_s": window_s,
               "busy_s": window_s, "gaps": {}},
        cell=cell, cj=load(CONFIG), chips=4, counters={},
        peaks={"flops": 197e12, "hbm_Bps": 819e9})


def test_flash_roofline_reads_the_kernel_at_two_rows_a_chip():
    read = run.metric_reader("flash_attention_roofline.train")
    sig = "custom-call bf16[2,16,2048,128] tpu_custom_call"
    ops = {f"shard_map.1036 {sig}": 0.005, f"rematted_computation.3 {sig}":
           0.005, "custom-call.9 custom-call bf16[16384,2048] "
           "tpu_custom_call": 1.0}
    work, _ = qwen3.flash_kernel_work(load(CONFIG), 2, 2048)
    # 20 calls in 10 ms; each needs its operations at the peak
    assert read(ctx(ops)) == pytest.approx(100 * 20 * work / 197e12 / 0.01)
    assert read(ctx({"fusion.1 fusion bf16[2,16,2048,128]": 1.0})) is None


def test_collective_share_counts_the_collectives_alone():
    read = run.metric_reader("collective_share.train")
    ops = {"all-gather.57 all-gather bf16[2048,2048]": 0.1,
           "psum.47 all-reduce bf16[2048]": 0.05,
           "async-collective-done.2 fusion bf16[2048,2048]": 0.2,
           "collective-permute-done.3 collective-permute-done "
           "bf16[512,1024]": 0.05,
           "all-to-all.1 all-to-all bf16[2,2048,4,512]": 0.1,
           "reduce-scatter.4 reduce-scatter f32[512]": 0.1,
           "fusion.3 fusion bf16[8,2048]": 1.0,
           "reduce_sum.2 reduce f32[8]": 0.3,
           "scatter-add.1 scatter f32[10,8]": 0.3}
    assert read(ctx(ops, window_s=4.0)) == pytest.approx(100 * 0.6 / 4.0)
    assert read(ctx({"fusion.3 fusion bf16[8]": 1.0})) == 0.0


@pytest.mark.parametrize("name,want", [
    ("flash_attention_roofline.train", 14.498684116653985),
    ("collective_share.train", 4.365383236178514),
    ("device_idle_share.train", 27.935617045254435)])
def test_readers_on_the_chips_reduced_trace(name, want):
    """The reduction of the traced run on four chips (seed 3141500101)
    gives what that run's result line read."""
    trace = load(REPO / "bench/testdata/qwen3_fsdp4.reduced.json")
    assert trace["n_devices"] == 4
    c = ctx({})
    c.trace = trace
    assert run.metric_reader(name)(c) == pytest.approx(want, rel=1e-9)


# ------------------------------------------------------- the tiny cell

@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    root = write_qwen3_root(tmp_path_factory.mktemp("qwen3"), chips=1)
    return root, measure_tiny(root, seed=2 ** 33 + 11)


def test_tiny_cell_runs_through_the_harness_on_one_device(one_device):
    _, (out, losses) = one_device
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["checks"]["bytes_mismatch"] == {"value": 0, "limit": 0}
    assert len(losses) == 3 and all(5.0 < x < 6.0 for x in losses)


def test_tiny_cell_traced_reports_the_host_metrics(one_device):
    root, _ = one_device
    out, _ = measure_tiny(root, seed=7, trace=True)
    assert out["correct"], out["checks"]
    # the CPU has no TPU plane: the device-trace metrics stay silent
    for name in ("device_idle_share.train", "flash_attention_roofline.train",
                 "collective_share.train"):
        assert name not in out["metrics"]
    for name in ("mfu.train", "input_wait_share.train", "chr.train",
                 "remote_bytes_per_token.train"):
        assert out["metrics"][name]["value"] >= 0, name


FOUR = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{src!r}, {repo!r}]
    import jax
    assert len(jax.devices()) == 4
    from bench.tests.test_qwen3_cell import measure_tiny, write_qwen3_root
    from bench.drivers import train
    meshes = []
    init = train.TrainJob.__init__

    def spy(self, *a):
        init(self, *a)
        meshes.append(dict(self.mesh.shape))
    train.TrainJob.__init__ = spy
    root = write_qwen3_root(Path({root!r}), chips=4)
    out, losses = measure_tiny(root, seed={seed})
    print(json.dumps({{"out": out, "losses": losses, "mesh": meshes[0]}}))
""")


def test_tiny_cell_on_a_four_device_mesh_matches_one_device(
        one_device, tmp_path):
    _, (_, losses1) = one_device
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(src=str(REPO / "src"), repo=str(REPO),
                       root=str(tmp_path), seed=2 ** 33 + 11)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["mesh"] == {"data": 4, "model": 1}
    assert got["out"]["correct"], got["out"]["checks"]
    assert got["out"]["device"]["count"] == 4
    # the same bfloat16 program on the same rows, its batch split over
    # four devices: its sums are ordered otherwise, so the losses agree to
    # float32 round-off of the loss's sums (2e-5 measured), not bitwise
    assert got["losses"] == pytest.approx(losses1, rel=1e-4)


# ------------------------------------------------ the chip's readings

def test_chip_readings_at_the_cells_size_meet_its_limits():
    limits = load(REPO / "bench/workloads" / f"{CELL}.json")["check"]
    got = load(CHIP_READINGS)

    def correct(r):
        return check.judge(dict(r, bytes_mismatch=0, nonfinite_losses=0),
                           limits)[0]

    assert len(got["program"]) >= 12
    assert len(got["control"]) >= 3 and len(got["half_batch"]) >= 3
    assert all(correct(r) for r in got["program"])
    assert not any(correct(r) for r in got["control"])
    assert not any(correct(r) for r in got["half_batch"])
