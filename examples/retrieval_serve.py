"""ParIS+ as the retrieval engine inside LM serving (kNN-LM-style).

The integration the framework is built around: the LM substrate produces
hidden-state vectors; ParIS+ indexes them; at decode time each new hidden
state queries the index for its nearest memorized states, whose next tokens
form a retrieval distribution that is interpolated with the LM logits
(Khandelwal et al.'s kNN-LM, with ParIS+ replacing the FAISS store).

Serving is *streamed*, *sharded*, and — new — *ingesting*: the datastore
lives in a ``MutableIndex`` behind an :class:`IngestingRouter`. Every
decoding sequence submits its retrieval query to the router as it
arrives; each shard's batcher coalesces the stream into padded
power-of-two batches and answers with ONE ``exact_knn_batch`` call over
its partition; the router merges the ownership-disjoint per-shard top
lists into the global exact k-NN. And because the index is now mutable,
the example *memorizes while it decodes*: after every step the freshly
produced (hidden state, chosen token) pairs are appended to the
datastore — each batch becomes a delta shard that is immediately a
routed, queryable shard — so later steps retrieve from earlier steps of
the same generation. A mid-stream compaction folds the accumulated
deltas into the base with linear merges and atomically rewires the
router; answers stay exact throughout. The pending queues are bounded
(``shed-oldest`` admission), so a decode storm degrades by shedding
stale retrievals instead of growing tail latency without bound.

    PYTHONPATH=src python examples/retrieval_serve.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import build_index
from repro.models import Model
from repro.serving.ingest import IngestingRouter
from repro.serving.kv_cache import pad_cache_to
from repro.training import data as data_mod

NUM_SHARDS = 2


def knn_mix_logits(lm_logits, dists, neighbor_tokens, vocab_size, lam):
    """kNN-LM interpolation, one scatter for the whole batch.

    lm_logits (B, V); dists (B, k) squared distances ascending;
    neighbor_tokens (B, k) the next-token of each retrieved state. The
    retrieval distribution is a softmax over -sqrt(d) whose per-token mass
    is the MAX over neighbors sharing that token — a single (B, k)
    segment-max scatter (``.at[rows, tokens].max``) instead of a Python
    double loop with one device round-trip per neighbor.
    """
    bsz, k = dists.shape
    w = jax.nn.softmax(-jnp.sqrt(jnp.maximum(dists, 0.0)), axis=1)
    rows = jnp.broadcast_to(jnp.arange(bsz)[:, None], (bsz, k))
    knn_logits = jnp.full((bsz, vocab_size), -1e9)
    knn_logits = knn_logits.at[rows, neighbor_tokens].max(jnp.log(w + 1e-9))
    return (1 - lam) * jax.nn.log_softmax(lm_logits) + \
        lam * jax.nn.log_softmax(knn_logits)


def main():
    cfg = dataclasses.replace(configs.get_smoke_config("granite-34b"),
                              d_model=64, vocab_size=512, dtype="float32")
    model = Model(cfg, remat=False)
    params = model.init_params(jax.random.PRNGKey(0))

    # --- "datastore" pass: run the LM over a corpus, index (hidden -> next
    # token) pairs with ParIS+. Hidden dim 64 is a perfectly ordinary data
    # series length for the index (w=16 segments of 4).
    print("building the hidden-state datastore ...")
    corpus = data_mod.bigram_batch(0, 16, 64, cfg.vocab_size)
    tokens = jnp.asarray(corpus["tokens"])
    logits, _, _ = model.apply(params, {"tokens": tokens})
    # hidden states via a second pass that returns pre-unembed activations:
    # cheap trick — unembed is linear, recover h @ W = logits; we just index
    # the logits vectors themselves as the series (same retrieval geometry).
    vecs = logits[:, :-1].reshape(-1, cfg.vocab_size)[:, :256]
    next_tokens = np.asarray(tokens[:, 1:]).reshape(-1)
    index = build_index(jnp.asarray(vecs), segments=16)
    print(f"indexed {index.num_series} (state, next-token) pairs")

    # --- serving pass: B sequences decode together; each step every
    # sequence submits its retrieval query to the ingesting router, which
    # fans it to every shard's batcher (base shards AND live delta
    # shards); each shard flushes the step's arrivals as one padded
    # engine batch over its partition and the router merges the per-shard
    # top lists into the exact global k-NN. After the step, the step's
    # own (state, token) pairs are appended — memorize-as-you-decode.
    lam, k, bsz, steps = 0.3, 8, 4, 8
    # Admission control rides the same router knobs as before (bounded
    # queues, shed-oldest); compaction is triggered explicitly below so
    # the example stays deterministic (compaction_policy=None disables
    # the background daemon).
    svc = IngestingRouter(
        index, NUM_SHARDS, k=k, max_batch=bsz, max_wait_ms=50.0,
        round_size=512, max_pending=4 * bsz, policy="shed-oldest",
        compaction_policy=None)
    prompts = tokens[:bsz, :8]
    logits, cache = model.prefill(params, {"tokens": prompts})
    cache = pad_cache_to(cache, 32)
    outs = [list(np.asarray(prompts[b])) for b in range(bsz)]
    last = logits[:, -1]  # (B, vocab)
    compactions = 0
    for i in range(steps):
        qs = np.asarray(last[:, :256])  # one retrieval query per sequence
        futs = [svc.submit(qs[b]) for b in range(bsz)]
        svc.drain()  # answers every shard's queued batch at the barrier
        res = [f.result() for f in futs]
        dists = jnp.asarray(np.stack([d for d, _ in res]))
        pos = np.stack([p for _, p in res])
        toks = jnp.asarray(next_tokens[pos])  # (B, k)
        mix = knn_mix_logits(last, dists, toks, cfg.vocab_size, lam)
        nxts = np.asarray(jnp.argmax(mix, axis=-1))
        for b in range(bsz):
            outs[b].append(int(nxts[b]))
        # memorize-as-you-decode: this step's states become a delta shard
        # (immediately queryable by step i+1) and their chosen tokens
        # extend the value table the retrieved positions point into.
        svc.append(qs)
        next_tokens = np.concatenate([next_tokens, nxts.astype(
            next_tokens.dtype)])
        if svc.mutable.num_deltas >= 4:  # fold deltas mid-stream
            svc.compact_now()
            compactions += 1
        last, cache = model.decode_step(
            params, {"tokens": jnp.asarray(nxts)[:, None]}, cache,
            jnp.int32(prompts.shape[1] + i))
    for b in range(bsz):
        print(f"seq {b} prompt + generated:", outs[b])
    s = svc.stats()
    ing = s["ingest"]
    print("(retrieval hits informed every step; ParIS+ answered",
          f"{s['answered']} streamed shard requests in",
          f"{s['batches']} batches (avg size {s['batch_size_avg']:.1f},",
          f"avg latency {s['latency_ms_avg']:.1f} ms,",
          f"merge avg {s['merge_ms_avg']:.2f} ms,",
          f"queue depth peak {s['queue_depth_peak']}, shed {s['shed']})",
          f"over a live datastore that grew {index.num_series} ->",
          f"{svc.num_series} vectors across {ing['appends']} appends,",
          f"{compactions} compactions ({s['retired_shards']} shards",
          "retired) — every answer exact at its point in the stream)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
