"""Streaming clustering service: multi-producer ingest + concurrent predict.

    PYTHONPATH=src python examples/streaming_service.py

Three producer threads push small (sub-k!) row chunks into the service
while a consumer thread answers predict queries the whole time — the
queue accumulates the first >= k rows, the background refresher folds
every micro-batch in with `partial_fit`, and each refresh publishes a
new immutable snapshot version that readers pick up without ever taking
a lock.
"""
import threading
import time

import numpy as np

from repro.api import FitConfig, NestedKMeans
from repro.data.synthetic import gaussian_blobs
from repro.serve import ClusterService, IngestQueue
from repro.util.env import enable_compile_cache

K, DIM, CHUNK = 32, 16, 12          # CHUNK < K on purpose
N_PER_PRODUCER = 4000


def producer(svc: ClusterService, pid: int, X: np.ndarray):
    rng = np.random.default_rng(pid)
    for i in range(0, len(X), CHUNK):
        svc.ingest(X[i:i + CHUNK],
                   ids=[(pid, int(j)) for j in range(i, min(i + CHUNK,
                                                            len(X)))])
        if rng.random() < 0.1:      # bursty traffic
            time.sleep(0.002)


def consumer(svc: ClusterService, queries: np.ndarray, out: dict):
    served, versions = 0, []
    while not out.get("stop"):
        snap = svc.snapshot
        if snap is None:            # nothing published yet: keep polling
            time.sleep(0.005)
            continue
        labels = svc.predict(queries)
        assert labels.shape == (len(queries),)
        versions.append(snap.version)
        served += 1
    out["served"] = served
    out["versions"] = versions


def main():
    enable_compile_cache()
    X, _ = gaussian_blobs(3 * N_PER_PRODUCER, k=K, dim=DIM, spread=5.0,
                          seed=0)
    parts = np.split(X, 3)
    queries = X[:256]

    km = NestedKMeans(FitConfig(k=K, b0=256, seed=0))     # unfitted!
    svc = ClusterService(km, micro_batch=512, flush_after_s=0.05,
                         queue=IngestQueue(max_rows=8192, dedup=True),
                         history_rows=4096).start()

    out = {}
    threads = [threading.Thread(target=producer, args=(svc, pid, part))
               for pid, part in enumerate(parts)]
    reader = threading.Thread(target=consumer, args=(svc, queries, out))
    t0 = time.time()
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # let the refresher catch up with the tail of the stream
    while svc.queue.depth and time.time() - t0 < 30:
        time.sleep(0.01)
    out["stop"] = True
    reader.join()
    svc.stop()

    m = svc.export_metrics()
    snap = svc.snapshot
    versions = out["versions"]
    assert versions == sorted(versions), "snapshot versions not monotone!"
    print(f"ingested {m['queue']['accepted']} rows from 3 producers "
          f"(deduped={m['queue']['deduped']}) in {time.time() - t0:.2f}s")
    print(f"background refreshes: {m['refresh']['count']} "
          f"({m['refresh']['rows']} rows) -> snapshot v{snap.version}, "
          f"batch MSE {snap.batch_mse:.4f}")
    print(f"concurrent predicts served: {out['served']} "
          f"(p50 {m['predict']['latency']['p50_s'] * 1e3:.2f}ms, "
          f"versions observed {versions[0] if versions else '-'}"
          f"..{versions[-1] if versions else '-'}, all monotone)")
    print(f"final codebook: {snap.k} cells over {snap.dim}d, "
          f"occupancy min/max {snap.counts.min():.0f}/"
          f"{snap.counts.max():.0f}")


if __name__ == "__main__":
    main()
