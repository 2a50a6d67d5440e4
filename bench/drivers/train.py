"""Train driver: the program's compiled train step, fed by
``CachedTokenPipeline`` through one IGTCache client in front of the
:mod:`bench.link` store, with optional co-tenant jobs on the same cache.

The loop repeats the body of ``repro.launch.train.run``: read a batch
through the pipeline, ``device_put`` it, run the step, bring the loss to
the host.  Set-up makes the weights from the seed, compiles the step,
fills the cache with the link's wait switched off, and then runs the
checked steps through that same loop with the wait on.  The window runs
the loop for the given seconds.  Afterwards the program's state is freed
and the reference repeats the checked steps on the same rows.

The seed makes the weights and every byte of the corpus; the order in
which the trainer samples the corpus comes from the cell's
``order_seed``, so that every seed asks the cache for the same work.
"""
from __future__ import annotations

import functools
import gc
import importlib
import math
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import CacheConfig, open_cache
from repro.core.baselines import bundle
from repro.data.pipeline import CachedTokenPipeline
from repro.launch.mesh import make_local_mesh
from repro.models.config import ShapeSpec
from repro.storage.datasets import make_dataset
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.train_step import lower_train_step, train_shardings

from bench import check
from bench.link import Content, Link, LinkStore, link_client, \
    mismatched_reads
from bench.reference.common import leaf_names, leaf_norms


class TrainJob:
    """Everything the window drives, built once in set-up."""

    def __init__(self, cell: dict, cj: dict, seed: int) -> None:
        job = cell["job"]
        self.cell, self.cj, self.seed = cell, cj, seed
        self.ref = importlib.import_module(f"bench.reference.{cj['family']}")
        fam = importlib.import_module(f"bench.families.{cj['family']}")
        cfg = fam.model_config(cj)
        self.devices = jax.devices()[:cell["chips"]]
        self.mesh = make_local_mesh(self.devices)
        shape = ShapeSpec("train", job["seq_len"], job["batch"], "train")
        self.opt = job["optimizer"]
        params_sh, opt_sh, self.batch_sh = train_shardings(cfg, shape,
                                                           self.mesh)
        self.params = jax.jit(functools.partial(self.ref.init_weights, cj),
                              out_shardings=params_sh)(
            self.ref.seed_key(seed))
        self.opt_state = jax.jit(init_state, out_shardings=opt_sh)(
            self.params)
        self.step_fn = lower_train_step(
            cfg, shape, self.mesh, remat=job["remat"],
            opt_cfg=AdamWConfig(**self.opt)).compile()
        # the step's scratch, which the allocator's peak does not count
        self.temp_bytes = self.step_fn.memory_analysis().temp_size_in_bytes
        self.tokens_per_step = job["batch"] * job["seq_len"]

        self.link = Link(**cell["link"])
        self.content = Content(seed)
        self.store = LinkStore(self.link, self.content)
        corpus = make_dataset(**cell["corpus"])
        self.store.add(corpus)
        for t in cell.get("tenants", []):
            self.store.add(make_dataset(**t["dataset"]))
        c = cell["cache"]
        self.client = link_client(open_cache(
            self.store, c["capacity"],
            cfg=CacheConfig(min_share=c["min_share"],
                            rebalance_quantum=c["rebalance_quantum"],
                            rebalance_period=c["rebalance_period"]),
            options=bundle(c["bundle"]), executor="threaded",
            fetch_bytes=True), self.store)
        self.pipe = CachedTokenPipeline(
            self.store, self.client, corpus.name, seq_len=job["seq_len"],
            batch=job["batch"], vocab=cj["vocab_size"],
            seed=job["order_seed"])
        self.batches = self.pipe.batches(epochs=1 << 30)
        self.tenants = [importlib.import_module(
            f"bench.drivers.{t['kind']}").Job(
            self.client, self.store, t["dataset"]["name"],
            **{k: v for k, v in t.items() if k not in ("kind", "dataset")})
            for t in cell.get("tenants", [])]
        self.input_s = 0.0
        self.losses: List[float] = []

    # -- the loop body the checked steps and the window share --------------
    def step(self) -> float:
        t0 = time.perf_counter()
        with TraceAnnotation("bench.read"):
            batch = next(self.batches)
        with TraceAnnotation("bench.device_put"):
            batch = jax.device_put(batch, self.batch_sh)
        self.input_s += time.perf_counter() - t0
        with TraceAnnotation("bench.step"):
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
        with TraceAnnotation("bench.loss_sync"):
            loss = float(metrics["loss"])
        self.losses.append(loss)
        return loss

    # -- set-up ------------------------------------------------------------
    def fill(self) -> None:
        """The cache as the job's earlier hours left it: reads with the
        link's wait switched off."""
        fill = self.cell["fill"]
        self.link.sleep_on = False
        try:
            for _ in range(fill["train_batches"]):
                next(self.batches)
            for t in self.tenants:
                for _ in range(fill["tenant_steps"]):
                    t.step(compute=False)
            if not self.client.flush(timeout=120.0):
                raise RuntimeError("prefetches still in flight after the fill")
        finally:
            self.link.sleep_on = True
        if self.pipe.stats.cache_hits and not self.store.local_bytes:
            raise RuntimeError("the cache reported hits but served none "
                               "from the host: its hit path has moved")
        for t in self.tenants:
            t.steps.clear()

    def checked_steps(self, n: int) -> dict:
        """The first ``n`` steps through :meth:`step`, with what the
        reference comparison reads: each loss, the per-leaf norm of the
        first gradient (from AdamW's first moment after one step), also
        per layer, and of the parameters' change over the ``n`` steps."""
        b1 = self.opt["b1"]
        norms = jax.jit(leaf_norms, static_argnames="per_layer")
        diff = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))
        names = leaf_names(self.params)
        p0 = jax.tree.map(jnp.copy, self.params)
        self.client.log = []
        losses, grads, layer_grads = [], None, None
        for i in range(n):
            losses.append(self.step())
            if i == 0:
                mu = self.opt_state.mu
                grads = np.asarray(norms(mu)) / (1 - b1)
                layer_grads = np.asarray(norms(mu, per_layer=True)) / (1 - b1)
        change = np.asarray(diff(self.params, p0))
        del p0
        self.check_log, self.client.log = self.client.log, None
        return {"losses": losses,
                "grad_norms": dict(zip(names, grads.tolist())),
                "layer_grad_norms": dict(zip(
                    leaf_names(self.params, per_layer=True),
                    layer_grads.tolist())),
                "change_norms": dict(zip(names, change.tolist()))}

    def rows(self, log) -> List[Dict[str, np.ndarray]]:
        """The checked steps' batches, decoded again from the store's
        bytes for the ranges the pipeline read."""
        seq = self.cell["job"]["seq_len"]
        out = []
        for batch in log:
            arr = np.stack([
                np.frombuffer(self.content.range(fp, off, n), "<u4",
                              count=seq + 1) % self.cj["vocab_size"]
                for fp, off, n, _ in batch]).astype(np.int32)
            out.append({"tokens": arr[:, :-1], "labels": arr[:, 1:]})
        return out

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, tracer) -> dict:
        link0 = self.link.counters()
        hits0, miss0 = self.pipe.stats.cache_hits, self.pipe.stats.cache_misses
        for t in self.tenants:
            t.start()
        self.client.log = []
        self.input_s, self.losses = 0.0, []
        tracer.start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.step()
            tracer.tick()
        t1 = time.perf_counter()
        tracer.stop()
        for t in self.tenants:
            t.stop()
        self.window_log, self.client.log = self.client.log, None
        link1 = self.link.counters()
        window_s = t1 - t0
        out = {"t_start": t0, "window_s": window_s,
               "steps": len(self.losses),
               "tokens": len(self.losses) * self.tokens_per_step,
               "input_s": self.input_s,
               "hits": self.pipe.stats.cache_hits - hits0,
               "misses": self.pipe.stats.cache_misses - miss0,
               "link_requests": link1[0] - link0[0],
               "link_bytes": link1[1] - link0[1],
               "link_queue_wait_s": link1[2] - link0[2],
               "nonfinite_losses": sum(not math.isfinite(x)
                                       for x in self.losses)}
        for t in self.tenants:
            out.update(t.window(t0, t1))
        return out

    def peak_bytes(self) -> Optional[int]:
        """The fullest chip's peak of allocated buffers plus the step's
        scratch from the compiled step's memory analysis."""
        stats = [d.memory_stats() or {} for d in self.devices]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        return None if None in peaks else max(peaks) + self.temp_bytes

    def close(self) -> None:
        for t in self.tenants:
            t.stop()
        self.pipe.close()
        self.client.close()
        self.params = self.opt_state = self.step_fn = None
        gc.collect()


def run(cell: dict, cj: dict, seed: int, seconds: float, tracer,
        reference: bool = True) -> dict:
    job = TrainJob(cell, cj, seed)
    try:
        job.fill()
        prog = job.checked_steps(cell["job"]["checked_steps"])
        counters = job.window(seconds, tracer)
        peak = job.peak_bytes()
    finally:
        job.close()
    rows = job.rows(job.check_log)
    n_reads, bad_reads = mismatched_reads(job.content,
                                          job.check_log + job.window_log)
    readings = {"bytes_mismatch": bad_reads,
                "nonfinite_losses": counters["nonfinite_losses"]}
    if reference:
        t0 = time.perf_counter()
        ref = job.ref.Reference(cj).train(
            jax.jit(functools.partial(job.ref.init_weights, cj))(
                job.ref.seed_key(seed)), rows, job.opt)
        readings.update(check.train_gaps(prog, ref))
        print(f"bench: reference {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
    ok, table = check.judge(readings, cell["check"])
    e2e = {"train_tokens_per_s": counters["tokens"] / counters["window_s"]}
    for t in job.tenants:
        e2e.update(t.end_to_end(counters))
    return {"correct": ok, "checks": table, "end_to_end": e2e,
            "counters": counters, "t_start": counters["t_start"],
            "attempted": counters["steps"] + n_reads,
            "failed": counters["nonfinite_losses"] + bad_reads,
            "memory_peak_bytes": peak}
