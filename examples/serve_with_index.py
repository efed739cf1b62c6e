"""End-to-end driver (the paper's kind: serving similarity search).

The paper's SV notes the technique "applies to high-dimensional vectors in
general ... such as deep-learning embeddings".  This example is that
application end to end:

  1. embed a corpus of token sequences with a (reduced) assigned LM,
  2. build the MESSI vector index over the embeddings,
  3. serve a LOOP of batched nearest-neighbour query batches (new
     sequences -> embed -> exact cosine top-k result lists), reporting
     p50/p99 per-batch latency — and, out-of-core, the block-cache
     hit-rate of the shared ``storage.SearchSession``.

With ``--index-path`` the index persists across launches (DESIGN.md §5):
the first run builds and saves it; every later run skips the corpus
embedding + build entirely and OPENS the file out-of-core — summaries on
device, raw embeddings streamed from disk per query batch — which is how
a server cold-starts against an index far larger than device memory.

    PYTHONPATH=src python examples/serve_with_index.py [--arch rwkv6-7b] \\
        [--k 5] [--index-path /tmp/corpus.dsix]
"""
import argparse
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import storage
from repro.configs import get_config
from repro.core import vector
from repro.models import common, transformer as T


def embed(params, cfg, tokens: jnp.ndarray) -> jnp.ndarray:
    """Mean-pooled final hidden state as the sequence embedding."""
    ctx = T.Ctx(cfg, None, (), "train")
    x = T.embed_inputs(params, {"tokens": tokens}, cfg, ctx)
    x, _, _ = T.decoder_stack(params, x, cfg, ctx)
    x = common.rmsnorm(x, params["final_norm"])
    return jnp.mean(x.astype(jnp.float32), axis=1)          # (B, d)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--corpus", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--k", type=int, default=5,
                    help="neighbours returned per query (exact top-k)")
    ap.add_argument("--batches", type=int, default=8,
                    help="serving loop length: query batches answered "
                         "back to back (out-of-core runs share one "
                         "SearchSession, so later batches hit its cache)")
    ap.add_argument("--cache-blocks", type=int, default=64,
                    help="SearchSession LRU capacity, in raw blocks "
                         "(out-of-core serving only)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="speculative block reads kept in flight ahead "
                         "of the walk (out-of-core only; answers are "
                         "bit-identical at every setting)")
    ap.add_argument("--group-blocks", type=int, default=1,
                    help="surviving blocks batched per refine dispatch, "
                         "one threshold sync per group (out-of-core "
                         "only; answers are bit-identical)")
    ap.add_argument("--readers", type=int, default=2,
                    help="block-cache reader threads (out-of-core only)")
    ap.add_argument("--concurrency", type=int, default=1,
                    help="tenant threads per batch (out-of-core only): "
                         "each thread submit()s its share of the queries "
                         "and blocks on its ticket; one coalesced drain "
                         "answers all of them through the shared cache")
    ap.add_argument("--index-path", default=None,
                    help="persisted index file: built+saved on first run, "
                         "opened out-of-core (no rebuild) afterwards")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = common.build_params(T.param_specs(cfg), key)
    rng = np.random.default_rng(0)

    # corpus: documents from 8 topical clusters (cluster = token offset)
    topics = rng.integers(0, 8, args.corpus)
    toks = ((topics[:, None] * 61 + rng.integers(0, 32,
             (args.corpus, args.seq))) % cfg.vocab).astype(np.int32)
    embed_fn = jax.jit(lambda p, t: embed(p, cfg, t))

    index = None
    if args.index_path and os.path.exists(args.index_path):
        extra = storage.read_meta(args.index_path)["extra"]
        # the embedding space is defined by (model, corpus): a mismatch on
        # either would silently serve neighbours from the wrong space
        want = {"kind": "vector", "corpus": args.corpus, "arch": args.arch}
        if {k: extra.get(k) for k in want} != want:
            raise SystemExit(f"{args.index_path} holds {extra}, not a "
                             f"vector index for {want} — delete it "
                             f"or pass a different --index-path")
        index = storage.open_index(args.index_path)
        print(f"opened {args.index_path} out-of-core: "
              f"{index.n_real} x {index.n} embeddings, "
              f"{index.n_blocks} blocks on disk")
    else:
        print(f"embedding {args.corpus} docs with {cfg.name} (reduced) ...")
        embs = []
        t0 = time.perf_counter()
        for i in range(0, args.corpus, 256):
            embs.append(embed_fn(params, jnp.asarray(toks[i:i + 256])))
        embs = jnp.concatenate(embs)
        jax.block_until_ready(embs)
        print(f"  {time.perf_counter()-t0:.1f}s -> embeddings {embs.shape}")

        if args.index_path:
            # persisted first launch goes through the staged build pipeline
            # (DESIGN.md §5): embeddings land in a SeriesStore next to the
            # index, and the sharded build records every stage in a
            # manifest — a launch killed mid-build resumes from the last
            # completed unit instead of rebuilding (the progress line says
            # so), and the finished file is byte-identical to
            # save_index(core.build(...))
            prepped = np.asarray(vector.prep_vectors(embs, True))
            store = storage.SeriesStore.write(args.index_path + ".series",
                                              prepped)
            print("building MESSI vector index (staged pipeline, "
                  "resumable) ...")
            index = storage.pipeline_build(
                store, args.index_path, w=16, card=256, capacity=256,
                normalize=False, workers=2,
                extra={"kind": "vector", "dim": embs.shape[-1],
                       "corpus": args.corpus, "arch": args.arch},
                progress=lambda m: print(f"  [build] {m}"))
            print(f"published index -> {args.index_path} (opened "
                  f"out-of-core; next launch skips embed+build entirely)")
        else:
            print("building MESSI vector index ...")
            index = vector.build_vector_index(embs, capacity=256)

    # serving traffic: --batches query batches, each perturbed members of
    # known clusters (fresh draws per batch, so only the index blocks their
    # survivors share are re-usable across batches — realistic locality)
    batches = []
    for _ in range(args.batches):
        qi = rng.choice(args.corpus, args.queries, replace=False)
        q_toks = toks[qi].copy()
        flip = rng.random(q_toks.shape) < 0.1
        q_toks[flip] = rng.integers(0, cfg.vocab, int(flip.sum()))
        batches.append((qi, embed_fn(params, jnp.asarray(q_toks))))
    dim = index.n

    session = None
    if index.device_resident:
        run = lambda qe: vector.search_vectors(index, qe, k=args.k)
        jax.block_until_ready(run(batches[0][1]).dist)  # compile warmup
    else:
        # compile warmup on a throwaway session: the jit cache is global
        # but the block cache is per-session, so the measured loop (and
        # its reported hit-rate) starts genuinely cold
        with storage.SearchSession(index, cache_blocks=2) as warmup:
            jax.block_until_ready(
                warmup.search(batches[0][1], k=args.k,
                              metric=vector.Cosine()).dist)
        session = storage.SearchSession(
            index, cache_blocks=args.cache_blocks, readers=args.readers,
            pipeline_depth=args.pipeline_depth,
            group_blocks=args.group_blocks)
        # the engine's Cosine metric owns the unit-norm prep, so the
        # session serves raw embeddings directly (DESIGN.md §4 matrix:
        # Cosine x cached backend)
        if args.concurrency > 1:
            # multi-tenant serving (DESIGN.md §9): split the batch over
            # tenant threads; every thread submits its slice and blocks
            # on its own ticket — the first to ask drains for everyone,
            # and answers are bit-identical to the single-tenant path
            def run(qe):
                n_t = min(args.concurrency, qe.shape[0])
                cuts = np.array_split(np.arange(qe.shape[0]), n_t)
                results = [None] * n_t
                admitted = threading.Barrier(n_t)

                def tenant(i):
                    t = session.submit(qe[cuts[i]], k=args.k,
                                       metric=vector.Cosine())
                    admitted.wait()   # all tenants in before anyone drains
                    results[i] = t.result()

                threads = [threading.Thread(target=tenant, args=(i,))
                           for i in range(n_t)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                first = results[0]
                return type(first)(
                    dist=jnp.concatenate([r.dist for r in results]),
                    idx=jnp.concatenate([r.idx for r in results]),
                    stats=first.stats, io=first.io)
        else:
            run = lambda qe: session.search(qe, k=args.k,
                                            metric=vector.Cosine())

    lat_ms = []
    for qi, q_embs in batches:                          # the serving loop
        t0 = time.perf_counter()
        res = run(q_embs)
        jax.block_until_ready(res.dist)
        lat_ms.append((time.perf_counter() - t0) * 1e3)
    p50, p99 = np.percentile(lat_ms, [50, 99])

    ids = np.asarray(res.idx)           # quality stats from the last batch
    cos = np.asarray(vector.cosine_scores(res, dim=dim))
    valid = ids >= 0                                    # k > corpus -> -1 pads
    hits = (topics[np.where(valid, ids, 0)] == topics[qi][:, None]) & valid
    same_topic = hits.sum() / max(valid.sum(), 1)
    self_hit = np.mean(ids[:, 0] == qi)
    print(f"served {args.batches} batches x {args.queries} queries "
          f"(top-{args.k}): p50 {p50:.1f} ms/batch  p99 {p99:.1f} ms/batch "
          f"({p50 / args.queries:.2f} ms/query at p50)")
    print(f"  exact self-retrieval@1: {100*self_hit:.0f}%   "
          f"same-topic neighbours@{args.k}: {100*same_topic:.0f}%")
    print(f"  rank-1 cosine {cos[:, 0].mean():.3f}  "
          f"rank-{args.k} cosine {cos[:, -1].mean():.3f}")
    print(f"  refined {float(np.mean(np.asarray(res.stats.series_refined))):.0f} "
          f"of {args.corpus} embeddings per query (pruning at work)")
    if session is not None:
        if args.concurrency > 1:
            print(f"  served by {args.concurrency} tenant threads per "
                  f"batch through one coalesced drain (answers identical "
                  f"to the single-tenant path)")
        print(f"  block cache ({args.cache_blocks} blocks): "
              f"{100 * session.hit_rate:.0f}% hit-rate over the session "
              f"({session.cache_hits} hits / {session.blocks_fetched} "
              f"disk fetches); last batch read {res.io.bytes_read:,} of "
              f"{res.io.bytes_scan:,} scan bytes "
              f"({100 * res.io.read_fraction:.0f}%)")
        session.close()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
