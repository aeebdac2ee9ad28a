"""Corpus-size sweep: exact-TPU vs TPU-IVF vs native C++ IVF retrieval.

    python perf/bench_retrieval_sweep.py            # 1e4, 1e5 (and 1e6 on TPU)
    BENCH_SIZES=10000,100000 BENCH_DIM=1024 python perf/bench_retrieval_sweep.py

Answers SURVEY.md §7 hard part 3 ("competitive at non-toy corpus sizes"):
for each corpus size, measures per-query search latency of the exact
matmul top-k (`TPUVectorStore`), the clustered TPU index
(`TPUIVFVectorStore`, reference Milvus GPU_IVF_FLAT defaults nlist=64
nprobe=16 — `common/utils.py:198-203`), and the C++ IVF
(`native/vecsearch.cpp`), plus IVF recall@10 against exact truth.
Prints one JSON line per (size, backend).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

DIM = int(os.environ.get("BENCH_DIM", "1024"))
N_QUERIES = int(os.environ.get("BENCH_QUERIES", "32"))
TOP_K = 10


def main() -> None:
    import jax

    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.native import NativeVectorStore
    from generativeaiexamples_tpu.retrieval.tpu import (
        TPUIVFVectorStore,
        TPUVectorStore,
    )

    platform = jax.devices()[0].platform
    if os.environ.get("BENCH_SIZES"):
        sizes = [int(s) for s in os.environ["BENCH_SIZES"].split(",")]
    else:
        sizes = [10_000, 100_000] + ([1_000_000] if platform != "cpu" else [])

    rng = np.random.default_rng(0)
    # Clustered corpus (documents cluster by topic; uniform-random vectors
    # are the degenerate no-structure worst case for ANY ivf index).
    n_centers = 256
    centers = rng.standard_normal((n_centers, DIM)).astype(np.float32) * 3

    for n in sizes:
        assign = rng.integers(0, n_centers, n)
        vecs = centers[assign] + rng.standard_normal((n, DIM)).astype(
            np.float32
        )
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        chunks = [Chunk(text=str(i), source="s") for i in range(n)]
        queries = [
            vecs[rng.integers(0, n)].tolist() for _ in range(N_QUERIES)
        ]

        def timed(store, label, truth=None):
            # ndarray passes the Sequence[Sequence[float]] contract; a
            # tolist() at 1M x 1024 would materialize ~30 GB of Python
            # floats per backend.
            store.add(chunks, vecs)
            store.search(queries[0], TOP_K)  # sync + compile + index build
            t0 = time.perf_counter()
            results = [store.search(q, TOP_K) for q in queries]
            per_query_ms = (time.perf_counter() - t0) / N_QUERIES * 1000
            # Batched: one dispatch for the whole query set — the
            # concurrent-serving shape.  Per-dispatch latency dominates
            # single-query search at small corpus sizes; batching
            # amortizes it away.
            store.search_batch(queries, TOP_K)  # compile the batch shape
            t0 = time.perf_counter()
            store.search_batch(queries, TOP_K)
            batch_ms = (time.perf_counter() - t0) / N_QUERIES * 1000
            out = {
                "bench": "retrieval-sweep",
                "backend": label,
                "corpus": n,
                "dim": DIM,
                "platform": platform,
                "latency_ms_per_query": round(per_query_ms, 3),
                "batched_ms_per_query": round(batch_ms, 3),
                "batch_size": N_QUERIES,
            }
            sets = [{h.chunk.text for h in r} for r in results]
            if truth is not None:
                out["recall@10"] = round(
                    float(
                        np.mean(
                            [len(a & b) / TOP_K for a, b in zip(truth, sets)]
                        )
                    ),
                    4,
                )
            print(json.dumps(out), flush=True)
            return sets

        def guarded(mk_store, label, truth=None):
            """One backend crashing (e.g. HBM OOM at a corpus size) must
            not cost the remaining rows of the sweep."""
            try:
                return timed(mk_store(), label, truth)
            except Exception as e:  # noqa: BLE001
                print(
                    json.dumps(
                        {
                            "bench": "retrieval-sweep",
                            "backend": label,
                            "corpus": n,
                            "error": str(e)[:200],
                        }
                    ),
                    flush=True,
                )
                return None

        truth = guarded(lambda: TPUVectorStore(DIM), "tpu-exact")
        guarded(
            lambda: TPUIVFVectorStore(
                DIM, nlist=64, nprobe=16, min_train_size=1000
            ),
            "tpu-ivf",
            truth,
        )
        try:
            timed(
                NativeVectorStore(
                    DIM, index_type="ivf", nlist=64, nprobe=16,
                    ivf_build_threshold=1000,
                ),
                "native-ivf",
                truth,
            )
        except Exception as e:  # noqa: BLE001 — C++ lib may be unbuilt
            print(
                json.dumps(
                    {
                        "bench": "retrieval-sweep",
                        "backend": "native-ivf",
                        "corpus": n,
                        "error": str(e)[:200],
                    }
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
