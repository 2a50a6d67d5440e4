"""A tiny cell written as files into a scratch checkout root, driven on
the CPU through the harness's own code path (``run.measure``)."""
import copy
import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
CELL = "mamba2-370m.train.oversub-scan"


def write_tiny_root(root: Path, name: str = "tiny.train.oversub-scan",
                    config: str = "tiny-mamba2") -> Path:
    """BENCHMARK.json with one tiny cell, its cell file and configuration:
    the mamba2 oversubscribed cell at a size the CPU runs in seconds."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = json.loads((REPO / "bench/workloads" / f"{CELL}.json").read_text())
    cj = json.loads((REPO / "bench/configs/mamba2-370m.json").read_text())
    cj.update(name=config, d_model=64, n_layer=2, vocab_size=256,
              ssm_cfg=dict(cj["ssm_cfg"], d_state=16, headdim=16,
                           chunk_size=32))
    cell = copy.deepcopy(cell)
    cell["job"].update(batch=4, seq_len=64)
    cell["corpus"].update(file_size=256 * 1024)
    cell["tenants"][0]["dataset"].update(n_dirs=8, files_per_dir=4,
                                         small_file_size=64 * 1024)
    cell["cache"].update(capacity=2 << 20, min_share=256 << 10,
                         rebalance_quantum=256 << 10)
    cell["link"].update(latency_s=0.002)
    cell["fill"].update(train_batches=20, tenant_steps=4)
    entry = dict(next(w for w in spec["workloads"] if w["name"] == CELL),
                 name=name, config=config)
    spec["workloads"] = [entry]
    spec["configs"] = [dict(spec["configs"][0], name=config,
                            file=f"bench/configs/{config}.json")]
    for kind in ("end_to_end", "per_layer"):
        spec[kind] = [dict(m, workloads=[name]) if "workloads" in m else m
                      for m in spec[kind]
                      if CELL in m.get("workloads", [CELL])]
    (root / "bench/workloads").mkdir(parents=True, exist_ok=True)
    (root / "bench/configs").mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench/metrics", root / "bench/metrics")
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    (root / "bench/workloads" / f"{name}.json").write_text(json.dumps(cell))
    (root / "bench/configs" / f"{config}.json").write_text(json.dumps(cj))
    return root


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    from bench import peaks
    # the CPU is no device the benchmark measures: a stand-in peak entry
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops": 1e12,
                                             "hbm_Bps": 1e11})
    return write_tiny_root(tmp_path)


def run_tiny(root: Path, name: str = "tiny.train.oversub-scan",
             trace: bool = False, seed: int = 2 ** 33 + 5,
             seconds: float = 1.0) -> dict:
    import jax
    from bench import run
    spec, entry, cell, cj = run.load_cell(name, root)
    return run.measure(spec, entry, cell, cj, seed, seconds, trace,
                       jax.devices(), root=root)
