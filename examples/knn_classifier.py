"""Paper Fig. 18 use-case: a k-NN time-series classifier backed by ParIS+.

Two synthetic classes of random walks (opposite drift); the classifier
finds each query's k nearest indexed series and votes.

    PYTHONPATH=src python examples/knn_classifier.py
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.core import build_index
from repro.core.classifier import KnnClassifier


def main():
    rng = np.random.default_rng(0)
    n_per, length = 20_000, 128
    print("generating two drift classes ...")
    a = (rng.standard_normal((n_per, length)) + 0.06).cumsum(axis=1)
    b = (rng.standard_normal((n_per, length)) - 0.06).cumsum(axis=1)
    raw = np.concatenate([a, b]).astype(np.float32)
    labels = np.concatenate([np.zeros(n_per, np.int32),
                             np.ones(n_per, np.int32)])

    print("indexing ...")
    index = build_index(jnp.asarray(raw))
    clf = KnnClassifier(index, labels, k=5)

    correct = idx_ms = brute_ms = 0
    trials = 20
    for _ in range(trials):
        drift = rng.choice([-0.06, 0.06])
        q = jnp.asarray((rng.standard_normal(length) + drift).cumsum(),
                        jnp.float32)
        t0 = time.time()
        pred = clf.predict(q)
        idx_ms += (time.time() - t0) * 1e3
        t0 = time.time()
        ref = clf.predict_brute(q)
        brute_ms += (time.time() - t0) * 1e3
        correct += (pred == (drift > 0) * 1) and (pred == ref)
    print(f"accuracy(+agreement with brute force): {correct}/{trials}")
    print(f"mean latency: index {idx_ms / trials:.1f}ms vs "
          f"brute {brute_ms / trials:.1f}ms "
          f"({brute_ms / max(idx_ms, 1e-9):.1f}x)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
