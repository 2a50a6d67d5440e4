"""End-to-end behaviour of the paper's system: the unified cache serving a
real JAX training pipeline + the serving engine, plus the headline
adaptivity claims at miniature scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import CacheConfig, IGTCache, bundle
from repro.core.types import MB
from repro.data.pipeline import CachedTokenPipeline, make_token_dataset
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import init_params
from repro.storage import RemoteStore
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.train_step import make_train_step


@pytest.fixture(scope="module")
def world():
    store = RemoteStore()
    store.add(make_token_dataset("corpus", n_shards=4, shard_bytes=8 * MB))
    cfg = CacheConfig(min_share=2 * MB, rebalance_quantum=2 * MB,
                      rebalance_period=5.0, block_size=1 * MB)
    return store, cfg


def test_pipeline_trains_through_cache(world):
    store, ccfg = world
    engine = IGTCache(store, 16 * MB, cfg=ccfg)
    cfg = reduced_config("qwen3-1.7b")
    pipe = CachedTokenPipeline(store, engine, "corpus", seq_len=32, batch=2,
                               vocab=cfg.vocab, background_prefetch=False)
    mesh = make_local_mesh()
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=5e-3, warmup_steps=2,
                                                    total_steps=100),
                                   mesh, None, remat="none"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params)
    losses = []
    it = pipe.batches(epochs=3)
    for i, b in enumerate(it):
        if i >= 12:
            break
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]            # it learns something
    assert pipe.stats.batches >= 12
    pipe.close()


def test_pipeline_epoch2_hits_cache(world):
    store, ccfg = world
    engine = IGTCache(store, 64 * MB, cfg=ccfg)   # corpus (32MB) fits
    pipe = CachedTokenPipeline(store, engine, "corpus", seq_len=32, batch=4,
                               vocab=1000, background_prefetch=False)
    n = len(pipe._samples) // 4
    it = pipe.batches(epochs=2)
    for i, _ in enumerate(it):
        if i >= 2 * n - 1:
            break
    assert engine.hit_ratio() > 0.45          # epoch 2 ~fully cached
    pipe.close()


def _first_batch(world):
    """Samples 0-3 of shard 0, read in order: all inside its first block."""
    store, ccfg = world
    engine = IGTCache(store, 16 * MB, cfg=ccfg)
    pipe = CachedTokenPipeline(store, engine, "corpus", seq_len=32, batch=4,
                               vocab=1000, background_prefetch=False,
                               access_pattern="sequential")
    batch = next(pipe.batches())
    pipe.close()
    assert all(off + pipe.sample_bytes <= ccfg.block_size
               for _, off in pipe._samples[:4])
    return store, pipe, batch


def test_pipeline_tokens_are_the_sample_bytes(world):
    """Each sample's tokens are its own bytes in the store, as little-endian
    uint32 words modulo the vocab, and bytes_read counts them."""
    store, pipe, batch = _first_batch(world)
    for i, (fp, off) in enumerate(pipe._samples[:4]):
        raw = bytes(store.fetch_range(fp, off, pipe.sample_bytes))
        words = np.frombuffer(raw, dtype="<u4")[:33]
        want = (words % 1000).astype(np.int32)
        np.testing.assert_array_equal(batch["tokens"][i], want[:-1])
        np.testing.assert_array_equal(batch["labels"][i], want[1:])
    assert pipe.stats.bytes_read == 4 * pipe.sample_bytes


def test_pipeline_samples_in_one_block_differ(world):
    _, _, batch = _first_batch(world)
    toks = batch["tokens"]
    assert all(not np.array_equal(toks[i], toks[j])
               for i in range(4) for j in range(i + 1, 4))


def test_serving_engine_with_rag_cache(world):
    from repro.serve.engine import Request, ServingEngine
    from repro.storage import make_dataset
    store, ccfg = world
    store.add(make_dataset("knowledge", "flat_files", n_files=200,
                           small_file_size=64 * 1024))
    engine = IGTCache(store, 8 * MB, cfg=ccfg)
    cfg = reduced_config("qwen3-1.7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    srv = ServingEngine(params, cfg, batch=2, max_seq=64,
                        cache_engine=engine, knowledge_dataset="knowledge",
                        retrieval_k=3)
    rng = np.random.default_rng(0)
    for rid in range(6):
        srv.submit(Request(rid, rng.integers(0, cfg.vocab, 4,
                                             dtype=np.int32), max_new=4))
    done = srv.run(max_steps=200)
    assert len(done) == 6
    assert all(len(r.output) == 4 for r in done)
    assert engine.stats.accesses > 0          # retrieval went through cache


def test_adaptive_beats_fixed_on_mixed_traffic(world):
    """The paper's core claim in miniature: adaptivity wins when sequential +
    random streams share one cache."""
    from repro.storage import make_dataset
    store = RemoteStore()
    store.add(make_dataset("scan", "flat_files", n_files=600,
                           small_file_size=128 * 1024))
    store.add(make_dataset("train", "flat_files", n_files=300,
                           small_file_size=128 * 1024))
    ccfg = CacheConfig(min_share=2 * MB, rebalance_quantum=2 * MB,
                       rebalance_period=2.0)
    import random as _r

    def run(name):
        eng = IGTCache(store, 24 * MB, cfg=ccfg, options=bundle(name))
        rng = _r.Random(0)
        scan_files = store.datasets["scan"].files
        train_files = store.datasets["train"].files
        t = 0.0
        si = 0
        for epoch in range(3):
            order = list(range(len(train_files)))
            rng.shuffle(order)
            for j in order:
                for f in (scan_files[si % len(scan_files)], train_files[j]):
                    out = eng.read(f.path, 0, f.size, t)
                    for pth, sz in out.prefetches:
                        eng.complete_prefetch(pth, sz, t)
                    t += 0.01
                si += 1
        return eng.hit_ratio()

    assert run("igtcache") > run("juicefs")
