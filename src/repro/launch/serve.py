"""Batched serving driver: prefill + greedy decode loop.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama-1.1b --reduced --batch 4 --prompt-len 32 \
        --gen 16

With ``--codebook K`` the server also maintains a k-means VQ codebook
over the token-embedding table, served through `repro.serve`: the
codebook is fitted once at startup (checkpointable with
``--checkpoint-dir`` / ``--save-every``, resumable with ``--resume``)
and then wrapped in a `ClusterService` — every served batch's
embeddings are INGESTED, not folded inline, so the background refresher
keeps the codebook fresh while decode traffic reads versioned snapshots
without ever waiting on a `partial_fit`. Decode output is tagged with
its codebook cell.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.api import CheckpointConfig, FitConfig, NestedKMeans
from repro.models import model as M
from repro.serve import ClusterService, IngestQueue
from repro.train import step as tstep


def build_codebook(E, k: int, seed: int, *,
                   checkpoint_dir: str | None = None,
                   save_every: int = 20,
                   resume: bool = False,
                   backend: str = "local",
                   trace_dir: str | None = None) -> NestedKMeans:
    """Fit the embedding codebook through the unified api.

    ``E`` is the data to cluster: an in-memory ``(n, d)`` array (the
    embedding table), or an on-disk `repro.data.store` chunk store —
    a directory path or an open `ChunkStore` — for embedding corpora
    bigger than host memory. Store-backed fits stream the nested prefix
    from disk on any backend; everything downstream (checkpointing,
    resume, the local hand-off) is identical.

    With ``checkpoint_dir`` the fit checkpoints its full loop state
    every ``save_every`` rounds and (``resume=True``) continues a killed
    fit bit-identically instead of restarting. ``resume`` without a
    checkpoint dir is a loud error — silently refitting from scratch is
    exactly what a resuming operator does not want.

    ``trace_dir`` attaches a `repro.obs.FitObserver` to the fit: every
    round's scalars and span timings land as JSONL under the
    directory (`python -m repro.obs summarize DIR`).

    ``backend`` selects the execution engine for the FIT: "local"
    (default), "mesh" (points sharded over the host devices), "xl"
    (points AND centroids sharded — the large-k regime) or "multihost"
    (the mesh engine across jax.distributed processes). The mesh is
    built over whatever devices are visible; checkpoints restore
    elastically across backends, so a fit checkpointed locally resumes
    sharded and vice versa. The returned estimator is always a LOCAL
    one — a sharded fit's outcome is adopted onto the local engine so
    downstream serving streams without rebuilding a sharded layout per
    micro-batch (partial_fit itself runs on any backend now).
    """
    if resume and not checkpoint_dir:
        raise ValueError(
            "--resume needs --checkpoint-dir: there is nowhere to "
            "resume from without a checkpoint store")
    from pathlib import Path

    from repro.data.store import ChunkStore
    if isinstance(E, (str, Path)):
        E = ChunkStore(E)
    n = E.n if isinstance(E, ChunkStore) else E.shape[0]
    ck = (CheckpointConfig(checkpoint_dir=checkpoint_dir,
                           save_every=save_every)
          if checkpoint_dir else None)
    mesh = None
    if backend in ("mesh", "xl"):
        import math
        n_dev = len(jax.devices())
        # widest model axis both the device count and k divide by —
        # degrading to m=1 (centroids unsharded) only when unavoidable,
        # and loudly, since an operator asked for xl to SHARD k
        m = math.gcd(n_dev, k) if backend == "xl" else 1
        if backend == "xl" and m == 1 and n_dev > 1:
            print(f"warning: backend='xl' cannot shard k={k} over "
                  f"{n_dev} devices (gcd 1); centroids stay replicated "
                  f"(equivalent to backend='mesh')")
        mesh = jax.make_mesh((n_dev // m, m), ("data", "model"))
    cfg = FitConfig(k=k, algorithm="tb", rho=float("inf"),
                    b0=min(2 * k, n), bounds="hamerly2",
                    max_rounds=200, seed=seed, checkpoint=ck,
                    backend=backend, data_axes=("data",),
                    model_axis="model", trace_dir=trace_dir)
    km = NestedKMeans(cfg, mesh=mesh)
    km.fit(E, resume=resume)
    if backend != "local":
        # hand the sharded outcome to a local estimator, so downstream
        # serving streams without standing up a sharded layout per
        # micro-batch. Only the (k, d)-sized cluster stats are pulled —
        # km.stats_ is host-reachable on every backend (multihost fits
        # gather them through the engine at fit time); gathering the
        # row-sharded per-point arrays would concentrate the whole
        # dataset's state on one device for nothing.
        import dataclasses
        out = km.outcome_
        stats = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)),
                             km.stats_)
        out = dataclasses.replace(
            out, state=dataclasses.replace(out.state, stats=stats))
        km = NestedKMeans(dataclasses.replace(cfg, backend="local"))
        km.adopt(out)
    return km


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--codebook", type=int, default=0, metavar="K",
                    help="maintain a K-cell VQ codebook over the "
                         "embedding table via repro.serve")
    ap.add_argument("--codebook-store", default=None, metavar="DIR",
                    help="fit the codebook from this on-disk "
                         "repro.data.store chunk store instead of the "
                         "embedding table (its d must equal the model's "
                         "embedding dim; the fit streams from disk)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint the codebook fit in-loop here")
    ap.add_argument("--save-every", type=int, default=20,
                    help="codebook checkpoint cadence in host rounds")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed codebook fit from "
                         "--checkpoint-dir (error without it)")
    ap.add_argument("--codebook-backend", default="local",
                    choices=("local", "mesh", "xl", "multihost"),
                    help="execution engine for the codebook fit: local "
                         "| mesh (points sharded) | xl (points + "
                         "centroids sharded, for large K) | multihost "
                         "(jax.distributed processes)")
    ap.add_argument("--trace-dir", default=None,
                    help="write repro.obs structured traces of the "
                         "codebook fit here (inspect with `python -m "
                         "repro.obs summarize DIR`)")
    args = ap.parse_args()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    B, P = args.batch, args.prompt_len
    cache_len = P + args.gen + (cfg.encoder.n_ctx
                                if cfg.family == "vlm" else 0)

    service = None
    E = None
    if args.codebook:
        E = np.asarray(params["embed"], np.float32)
        t0 = time.time()
        source = args.codebook_store or E
        codebook = build_codebook(source, args.codebook, args.seed,
                                  checkpoint_dir=args.checkpoint_dir,
                                  save_every=args.save_every,
                                  resume=args.resume,
                                  backend=args.codebook_backend,
                                  trace_dir=args.trace_dir)
        what = (f"store {args.codebook_store}" if args.codebook_store
                else f"{E.shape} embeddings")
        print(f"codebook: k={args.codebook} over {what} "
              f"in {time.time() - t0:.2f}s "
              f"(rounds={codebook.n_rounds_}, "
              f"converged={codebook.converged_})")
        # background refresh: served embeddings are queued, folded in by
        # the refresher thread, and published as versioned snapshots;
        # dedup on token id keeps each embedding's contribution unique
        service = ClusterService(
            codebook, micro_batch=256, flush_after_s=0.05,
            queue=IngestQueue(max_rows=4096, dedup=True)).start()
    elif args.resume or args.checkpoint_dir or args.trace_dir:
        ap.error("--checkpoint-dir/--resume/--trace-dir only apply to "
                 "the codebook fit; pass --codebook K")

    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, P)))}
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros(
            (B, cfg.encoder.n_ctx, cfg.encoder.d_frontend), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros(
            (B, cfg.encoder.n_ctx, cfg.d_model), jnp.bfloat16)

    prefill = jax.jit(tstep.make_prefill_step(cfg, cache_len=cache_len))
    decode = jax.jit(tstep.make_decode_step(cfg), donate_argnums=(2,))

    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)

    out = [np.asarray(tok)]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok))
        if service is not None:
            # stream the served embeddings toward the refresher; token
            # ids double as dedup keys ("each sample exactly once")
            ids = np.asarray(tok).ravel()
            service.ingest(E[ids], ids=ids.tolist())
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.concatenate(out, axis=1)
    print(f"{args.arch}: prefill {B}x{P} in {t_prefill * 1e3:.1f}ms; "
          f"{args.gen - 1} decode steps in {t_decode * 1e3:.1f}ms "
          f"({B * (args.gen - 1) / max(t_decode, 1e-9):.0f} tok/s)")
    print("generated token ids (row 0):", gen[0].tolist())

    if service is not None:
        # tag output tokens with their codebook cell (router/dedup view)
        cells = service.predict(E[gen[0]])
        print("codebook cells  (row 0):", cells.tolist())
        service.stop()               # final flush of the ingest queue
        m = service.export_metrics()
        snap = service.snapshot
        print(f"codebook service: {m['refresh']['count']} background "
              f"refreshes over {m['refresh']['rows']} embeddings, "
              f"snapshot v{snap.version} "
              f"(deduped={m['queue']['deduped']}, "
              f"batch MSE {snap.batch_mse:.5f})")


if __name__ == "__main__":
    main()
