"""Serve a small LM with batched requests; RAG retrievals flow through the
unified cache (a skewed stream → the cache converges to LRU for it).

The retrieval cache is a ``CacheClient`` (``open_cache``): serving runs on
the wall clock, so prefetch candidates execute on the background
``ThreadedExecutor`` instead of inside the request path.

    PYTHONPATH=src python examples/serve_llm.py --requests 12
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.configs import reduced_config
from repro.core import CacheConfig, open_cache
from repro.core.types import MB
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServingEngine
from repro.storage import RemoteStore, make_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    use_compile_cache()
    cfg = reduced_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))

    store = RemoteStore()
    store.add(make_dataset("knowledge", "flat_files", n_files=500,
                           small_file_size=64 * 1024))
    cache = open_cache(store, 16 * MB,
                       cfg=CacheConfig(min_share=2 * MB,
                                       rebalance_quantum=2 * MB),
                       executor="threaded")
    srv = ServingEngine(params, cfg, batch=args.batch, max_seq=128,
                        cache_engine=cache, knowledge_dataset="knowledge",
                        retrieval_k=4)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, rng.integers(3, 8),
                              dtype=np.int32)
        srv.submit(Request(rid, prompt, max_new=args.max_new))
    done = srv.run()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s on CPU)")
    for r in done[:4]:
        print(f"  req{r.rid}: retrieved {r.retrieved} passages → "
              f"tokens {r.output}")
    cache.flush(timeout=5.0)
    s = cache.snapshot()
    pattern = next((c.effective_pattern().value
                    for _p, c in cache.iter_workload_cmus()), "?")
    print(f"retrieval cache: CHR={s['hit_ratio']:.3f} over "
          f"{s['hits']+s['misses']} passage reads (pattern: {pattern}; "
          f"executor: {s['executor']})")
    cache.close()


if __name__ == "__main__":
    main()
