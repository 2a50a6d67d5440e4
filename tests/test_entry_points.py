"""The normal entry points, rehearsed on the CPU: the trainer's CLI end to
end at a reduced size, the compile-cache placement, and chip_smoke.py's
refusal to run anywhere but a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, train

ROOT = Path(__file__).resolve().parent.parent


def test_train_main_reduced_mamba2(tmp_path, monkeypatch):
    # a set JAX_COMPILATION_CACHE_DIR is left to JAX (read at import, so
    # nothing is cached here) and the trainer sets no cache path itself
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_compilation_cache_dir
    ckpt = tmp_path / "ckpt"
    assert train.main(["--arch", "mamba2-370m", "--reduced", "--steps", "3",
                       "--ckpt-dir", str(ckpt)]) == 0
    assert jax.config.jax_compilation_cache_dir == before
    assert (ckpt / "LATEST").read_text().strip() == "step_00000003"


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert isinstance(exc.value.code, str)       # exit status 1
    assert "platform 'cpu'" in exc.value.code
    assert capsys.readouterr().out == ""         # no result line
