"""Batched serving engine: continuous batching over a decode step, with
RAG-style retrieval reads flowing through IGTCache (a *skewed* stream the
cache learns to LRU).

The engine keeps a fixed decode batch; finished sequences' slots are refilled
from the request queue (continuous batching).  Retrieval is simulated: each
request reads k passages from the knowledge dataset through the cache before
its prompt is admitted — that is the paper's RAG workload shape.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import CacheClient, IGTCache, NullExecutor
from ..models.config import ModelConfig
from ..models.transformer import decode_step, forward, init_decode_state


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S_prompt,)
    max_new: int = 16
    retrieved: int = 0
    output: List[int] = field(default_factory=list)
    submitted: float = 0.0
    finished: float = 0.0


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, *, batch: int = 4,
                 max_seq: int = 512,
                 cache_engine: Optional["IGTCache | CacheClient"] = None,
                 knowledge_dataset: Optional[str] = None,
                 retrieval_k: int = 4, zipf_a: float = 1.3,
                 seed: int = 0) -> None:
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        if cache_engine is not None and not isinstance(cache_engine,
                                                       CacheClient):
            # bare kernel: wrap it so its prefetch candidates are cancelled
            # rather than silently dropped (the kernel's pending table
            # would otherwise suppress re-issuing those blocks forever)
            cache_engine = CacheClient(cache_engine,
                                       executor=NullExecutor())
        self.cache = cache_engine
        self.knowledge = knowledge_dataset
        self.retrieval_k = retrieval_k
        self.rng = np.random.default_rng(seed)
        self.zipf_a = zipf_a
        self.queue: Deque[Request] = deque()
        self.done: List[Request] = []
        self._slots: List[Optional[Request]] = [None] * batch
        self.state = init_decode_state(cfg, batch, max_seq)
        self._decode_jit = jax.jit(
            lambda p, s, t: decode_step(p, cfg, s, t))
        self.decode_compiled = None     # jax.stages.Compiled, after warmup
        self.compile_s = 0.0

    def _decode(self, toks: jax.Array):
        """One decode step; the first call compiles it ahead of time, so
        ``compile_s`` and the compiled program can be read back."""
        if self.decode_compiled is None:
            t0 = time.perf_counter()
            self.decode_compiled = self._decode_jit.lower(
                self.params, self.state, toks).compile()
            self.compile_s = time.perf_counter() - t0
        return self.decode_compiled(self.params, self.state, toks)

    # ---------------------------------------------------------------- admit
    def submit(self, req: Request) -> None:
        req.submitted = time.monotonic()
        self.queue.append(req)

    def _retrieve(self, req: Request) -> None:
        """RAG retrieval: zipf-hot passage reads through the unified cache
        client (prefetch candidates run on its executor)."""
        if self.cache is None or self.knowledge is None:
            return
        ds = self.cache.meta.datasets[self.knowledge]
        n = len(ds.files)
        for _ in range(self.retrieval_k):
            r = int((self.rng.zipf(self.zipf_a) - 1) % n)
            f = ds.files[r]
            self.cache.read(f.path, 0, min(f.size, 64 * 1024),
                            time.monotonic())
            req.retrieved += 1

    def _admit(self) -> None:
        for i in range(self.batch):
            if self._slots[i] is None and self.queue:
                req = self.queue.popleft()
                self._retrieve(req)
                self._slots[i] = req

    # ----------------------------------------------------------------- step
    def run(self, max_steps: int = 1000) -> List[Request]:
        """Decode until queue + slots drain (token-level continuous batching).

        Prompts are fed token-by-token through the decode path (simple and
        uniform; a production prefill path exists in serve_step.py)."""
        feed_pos = [0] * self.batch
        for _ in range(max_steps):
            self._admit()
            if all(s is None for s in self._slots) and not self.queue:
                break
            toks = np.zeros((self.batch, 1), np.int32)
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                if feed_pos[i] < len(req.prompt):
                    toks[i, 0] = req.prompt[feed_pos[i]]
                elif req.output:
                    toks[i, 0] = req.output[-1]
            logits, self.state = self._decode(jnp.asarray(toks))
            nxt = np.asarray(logits[:, -1].argmax(-1))
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                if feed_pos[i] < len(req.prompt):
                    feed_pos[i] += 1
                    if feed_pos[i] == len(req.prompt):
                        req.output.append(int(nxt[i]))
                else:
                    req.output.append(int(nxt[i]))
                    if len(req.output) >= req.max_new:
                        req.finished = time.monotonic()
                        self.done.append(req)
                        self._slots[i] = None
                        feed_pos[i] = 0
        return self.done
