"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers and compiles a kernel's forward, or its
forward plus backward, at the widths mamba2-370m and qwen3-1.7b train at,
with the TPU compiler for a chip that is described, not attached.  The
compiled program must hold the kernel (``tpu_custom_call``).  This catches
what interpret mode cannot: block shapes the tiling refuses, unsupported
in-kernel ops, VMEM overruns, and kernels without a differentiation rule.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_chunk_pallas

BF16, F32 = jnp.bfloat16, jnp.float32

# (kernel, argument shapes/dtypes) at real widths
CASES = {
    # mamba2-370m: d_model 1024, batch 8 x seq 2048 tokens
    "rmsnorm_d1024": (rmsnorm_pallas, [((16384, 1024), BF16),
                                       ((1024,), BF16)]),
    # qwen3-1.7b: d_model 2048
    "rmsnorm_d2048": (rmsnorm_pallas, [((16384, 2048), BF16),
                                       ((2048,), BF16)]),
    # qwen3-1.7b: 16 query / 8 kv heads of 128, seq 2048
    "flash_qwen3": (flash_attention_pallas, [((2, 2048, 16, 128), BF16),
                                             ((2, 2048, 8, 128), BF16),
                                             ((2, 2048, 8, 128), BF16)]),
    # mamba2-370m: chunk 256, 32 heads of 64, state 128, 8 chunks
    "ssd_mamba2": (ssd_chunk_pallas, [((8, 8, 256, 32, 64), F32),
                                      ((8, 8, 256, 32), F32),
                                      ((8, 8, 256, 128), F32),
                                      ((8, 8, 256, 128), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache: keep it off so these compiles neither write nor warn
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("mode", ["forward", "backward"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, mode, one_chip):
    kernel, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    if mode == "forward":
        fn = kernel
    else:
        def loss(*a):
            return sum(o.astype(F32).sum()
                       for o in jax.tree.leaves(kernel(*a)))
        fn = jax.value_and_grad(loss, argnums=tuple(range(len(args))))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _custom_call_results(hlo: str) -> list:
    """Result shape of each ``tpu_custom_call`` in an HLO text, layouts
    dropped: ``bf16[2,16,2048,128]`` or a tuple ``(f32[..], ..)``."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            line = re.sub(r"\{[^{}]*\}", "", line)
            out.append(re.search(r" = (.+?) custom-call\(", line).group(1))
    return out


def test_flash_backward_is_pallas_for_v5e(one_chip):
    """At qwen3-1.7b's per-chip widths, value_and_grad through the flash
    kernel compiles to Pallas calls for the backward too: no float32
    (.., 2048, 512) score blocks of the jnp oracle's VJP, and exactly one
    call whose single result is the attention output bf16[2,16,2048,128]
    (the forward), so a reader that finds the forward by that result
    counts no backward call."""
    _, shapes = CASES["flash_qwen3"]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]

    def loss(q, k, v):
        return flash_attention_pallas(q, k, v).astype(F32).sum()

    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    results = _custom_call_results(hlo)
    assert results.count("bf16[2,16,2048,128]") == 1, results
    backward = [r for r in results if r != "bf16[2,16,2048,128]"]
    assert any("bf16[2,8,2048,128]" in r for r in backward), results  # dk, dv
    assert "f32[2,16,2048,128]" in backward, results                  # dq
    assert not re.search(r"f32\[(\d+,)*2048,512\]", hlo)
