"""Cluster an LM's token-embedding table with tb-inf (VQ / semantic dedup).

The classic application of web-scale k-means inside an LM stack: build a
k-codebook over the (vocab, d_model) embedding table — usable for
embedding compression, semantic dedup, or routing analysis. Uses the
reduced tinyllama config (full configs are dry-run-only on this box) and
the unified `repro.api` estimator.

    PYTHONPATH=src python examples/cluster_embeddings.py
"""
import jax
import numpy as np

from repro import configs
from repro.api import FitConfig, NestedKMeans
from repro.models import model as M
from repro.util.env import enable_compile_cache

enable_compile_cache()
cfg = configs.get_reduced("tinyllama-1.1b")
params = M.init_params(jax.random.PRNGKey(0), cfg)
E = np.asarray(params["embed"], np.float32)          # (vocab, d)
print(f"embedding table: {E.shape}")

K = 32
km = NestedKMeans(FitConfig(k=K, algorithm="tb", rho=float("inf"),
                            b0=128, bounds="hamerly2", max_rounds=200,
                            seed=0)).fit(E)
print(f"tb-inf codebook: converged={km.converged_} rounds={km.n_rounds_}")

mse = -km.score(E) / E.shape[0]
print(f"VQ reconstruction MSE: {mse:.6f}")

# codebook utilisation via the estimator's inference surface
a = km.predict(E)
sizes = np.bincount(a, minlength=K)
print(f"codebook usage: min={sizes.min()} max={sizes.max()} "
      f"empty={int((sizes == 0).sum())}")
compression = E.shape[0] * E.shape[1] / (K * E.shape[1] + E.shape[0])
print(f"compression ratio vs raw table: {compression:.1f}x")

# -- out-of-core: the same fit streamed off disk ----------------------------
# For embedding corpora that don't fit in host memory, write them once
# to a chunked store (repro.data.store) and hand the store path to the
# estimator — the fit streams the nested prefix from disk. Done here
# with the same table so the in-memory run above is the reference.
import tempfile                                              # noqa: E402

from repro.data.store import write_store                     # noqa: E402

store_dir = tempfile.mkdtemp(prefix="embed_store_") + "/table"
write_store(store_dir, E, chunk_rows=4096)
km_disk = NestedKMeans(FitConfig(k=K, algorithm="tb", rho=float("inf"),
                                 b0=128, bounds="hamerly2",
                                 max_rounds=200, seed=0)).fit(store_dir)
print(f"streamed-from-disk codebook: converged={km_disk.converged_} "
      f"rounds={km_disk.n_rounds_} "
      f"VQ-MSE {-km_disk.score(E) / E.shape[0]:.6f}")
