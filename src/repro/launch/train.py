"""End-to-end training driver: IGTCache-fed data pipeline → sharded train
step → checkpoint/restart — the paper's cache as the first-class data plane
of an LM trainer.

Example (CPU, ~15M model, a few hundred steps):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
        --steps 100 --batch 4 --seq 256

``--arch <id>`` selects any assigned architecture; ``--reduced`` swaps in the
same-family smoke config so the trainer runs on CPU.  The mesh spans this
host's devices as ``("data", "model") = (n, 1)``: params, optimizer state and
batch shard by the logical rules, so with n > 1 it runs FSDP over the chips.
"""
from __future__ import annotations

import argparse
import functools
import time
from dataclasses import dataclass
from typing import List, Optional

import jax

from ..configs import get_config, reduced_config
from ..core import CacheConfig, bundle_client
from ..core.types import MB
from ..data.pipeline import CachedTokenPipeline, make_token_dataset
from ..models.config import ModelConfig, ShapeSpec
from ..models.transformer import init_params
from ..storage.object_store import RemoteStore
from ..train.checkpoint import CheckpointManager
from ..train.fault import PreemptionGuard, StragglerDetector
from ..train.optimizer import AdamWConfig, init_state
from ..train.train_step import lower_train_step, train_shardings
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh


@dataclass
class TrainSetup:
    """Params and optimizer state placed on a mesh, and the compiled step."""

    params: object
    opt_state: object
    batch_shardings: dict
    step: object                 # jax.stages.Compiled
    compile_s: float
    kernel_calls: int            # Pallas call sites in the compiled step
    temp_bytes: Optional[int]    # step scratch per device (memory analysis)


def setup_training(cfg: ModelConfig, shape: ShapeSpec, mesh,
                   opt_cfg: AdamWConfig, seed: int = 0) -> TrainSetup:
    """Initialise params (from ``seed``) and AdamW state directly in their
    shardings on ``mesh`` and compile the train step ahead of time.  The
    values do not depend on the mesh, so two meshes start from equal
    params."""
    params_sh, opt_sh, batch_sh = train_shardings(cfg, shape, mesh)
    params = jax.jit(functools.partial(init_params, cfg),
                     out_shardings=params_sh)(jax.random.PRNGKey(seed))
    opt_state = jax.jit(init_state, out_shardings=opt_sh)(params)
    t0 = time.perf_counter()
    step = lower_train_step(cfg, shape, mesh, remat="full",
                            opt_cfg=opt_cfg).compile()
    compile_s = time.perf_counter() - t0
    mem = step.memory_analysis()
    return TrainSetup(params, opt_state, batch_sh, step, compile_s,
                      step.as_text().count("tpu_custom_call"),
                      mem.temp_size_in_bytes if mem is not None else None)


@dataclass
class TrainReport:
    compile_s: float
    kernel_calls: int            # tpu_custom_call sites in the step program
    step_s: List[float]          # host clock, batch read → loss on host
    input_s: List[float]         # part of step_s spent reading the batch
    losses: List[float]
    hit_ratio: float
    bytes_read: int              # token bytes the cache client returned
    peak_bytes: Optional[int]    # max over devices; None if not reported
    temp_bytes: Optional[int]    # step scratch per device (memory analysis)


def _peak_bytes(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between checkpoints; 0 writes none")
    ap.add_argument("--cache-mb", type=int, default=256)
    ap.add_argument("--cache-bundle", default="igtcache",
                    help="igtcache | juicefs | prefetch_none | ...")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def run(argv=None) -> TrainReport:
    """The trainer behind :func:`main`; returns what it measured."""
    args = _parse(argv)
    use_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh()
    shape = ShapeSpec("train", args.seq, args.batch, "train")

    # ---- the paper's technique as the data plane -------------------------
    store = RemoteStore()
    n_shards = 8
    shard_bytes = max(8 * MB, args.batch * (args.seq + 1) * 4 * args.steps
                      // n_shards)
    store.add(make_token_dataset("train_corpus", n_shards, shard_bytes))
    cache_cfg = CacheConfig(min_share=16 * MB, rebalance_quantum=16 * MB,
                            rebalance_period=10.0)
    # one constructor path: the client owns prefetch execution (per-shard
    # background workers) and byte movement; the trainer never loops over
    # candidates by hand
    client = bundle_client(args.cache_bundle, store, args.cache_mb * MB,
                           cfg=cache_cfg, executor="threaded")
    engine = client.engine
    pipe = CachedTokenPipeline(store, client, "train_corpus",
                               seq_len=args.seq, batch=args.batch,
                               vocab=cfg.vocab)

    # ---- model / optimizer ------------------------------------------------
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
    ts = setup_training(cfg, shape, mesh, opt_cfg)
    params, opt_state = ts.params, ts.opt_state
    print(f"[train] {cfg.name}: step compiled in {ts.compile_s:.1f}s "
          f"(mesh {dict(mesh.shape)}, {ts.kernel_calls} Pallas call sites)",
          flush=True)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_every else None
    start_step = 0
    if args.resume and ckpt is not None and ckpt.latest_step() is not None:
        params_sh, opt_sh, _ = train_shardings(cfg, shape, mesh)
        (params, opt_state), extra = ckpt.restore(
            (params, opt_state), shardings=(params_sh, opt_sh))
        start_step = extra.get("step", ckpt.latest_step())
        print(f"[train] resumed from step {start_step}")

    straggler = StragglerDetector()

    def on_preempt():
        if ckpt is not None:
            ckpt.save(step, (params, opt_state), {"step": step})
        print(f"[train] preempted at step {step}")

    step = start_step
    t_start = time.time()
    step_s: List[float] = []
    input_s: List[float] = []
    losses: List[float] = []
    with PreemptionGuard(on_preempt):
        it = pipe.batches(epochs=1000)
        for step in range(start_step, args.steps):
            t0 = time.perf_counter()
            batch = jax.device_put(next(it), ts.batch_shardings)
            t_in = time.perf_counter() - t0
            params, opt_state, metrics = ts.step(params, opt_state, batch)
            loss = float(metrics["loss"])     # waits for the step
            dt = time.perf_counter() - t0
            step_s.append(dt)
            input_s.append(t_in)
            losses.append(loss)
            straggler.record(0, dt)
            if (step + 1) % args.log_every == 0:
                s = engine.snapshot()
                print(f"[train] step {step+1:5d} loss {loss:7.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"CHR {s['hit_ratio']:.3f} "
                      f"({dt:.3f}s/step, input {t_in:.3f}s)", flush=True)
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1, (params, opt_state),
                                {"step": step + 1})
    if ckpt is not None:
        ckpt.wait()
        ckpt.save(args.steps, (params, opt_state), {"step": args.steps})
    pipe.close()
    client.close()
    s = engine.snapshot()
    print(f"[train] done: {args.steps - start_step} steps in "
          f"{time.time() - t_start:.1f}s; final loss {losses[-1]:.4f} "
          f"(first {losses[0]:.4f}); cache CHR {s['hit_ratio']:.3f}, "
          f"prefetch_hits {s['prefetch_hits']}")
    return TrainReport(ts.compile_s, ts.kernel_calls, step_s, input_s,
                       losses, s["hit_ratio"], pipe.stats.bytes_read,
                       _peak_bytes(mesh.devices.flat), ts.temp_bytes)


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
