"""Traffic kind ``predict_open_loop``: requests to a served codebook,
sent on a schedule whatever the server does.

Set-up makes a pool of query rows and a codebook of ``k`` rows on the
device from the seed (the codebook is not fitted), holds both on the
host, publishes the codebook as a ``CodebookSnapshot``, calls
``predict`` on each request size until its program is compiled and warm,
then sends ``WARMUP_SECONDS`` of traffic, so that what the first
requests of a process warm up is set-up too.

The schedule of a window of S seconds at rate R holds M = round(R * S)
requests. Their sizes are a fixed multiset (``sizes`` in proportion to
``weights``, rounded) and their gaps exponential draws scaled so that
they add up to S, both in one order drawn from ``BASE_SEED``: every run
sends the same sizes at the same times (on a TPU v5 lite host the order
alone moved a window's p95 by 13% from seed to seed). The run's seed
makes the pool and the codebook and picks where in the pool each
request's rows start.

A generator thread hands each request to the server at its due time;
one server thread takes them in due order, calls
``CodebookSnapshot.predict`` and keeps the labels. A request's latency
runs from when it was due until its labels are on the host. A request
not answered within ``GRACE_S`` after the window counts as failed. A
traced run sends ``TRACE_SECONDS`` of the same schedule.

Traffic parameters (``traffic/<mix>.json``): ``rate_per_s``, ``sizes``,
``weights``, ``pool_rows``.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

import numpy as np

BASE_SEED = 0           # the schedule's gaps and order, in every run
WARMUP_CALLS = 20       # predict calls per request size in set-up
WARMUP_SECONDS = 1.0    # traffic sent in set-up
TRACE_SECONDS = 3.0     # traffic sent in a traced window
GRACE_S = 60.0          # wait past the window before a request fails


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray       # due time of each request, from window start
    sizes: np.ndarray       # rows per request
    starts: np.ndarray      # first pool row of each request


def schedule(p: Dict[str, Any], seed: int, seconds: float,
             pool_rows: int) -> Schedule:
    m = max(1, int(round(p["rate_per_s"] * seconds)))
    base = np.random.default_rng(BASE_SEED)
    gaps = base.exponential(1.0, m)
    gaps *= seconds / gaps.sum()
    w = np.asarray(p["weights"], np.float64)
    counts = np.floor(w / w.sum() * m).astype(int)
    counts[0] += m - counts.sum()
    sizes = base.permutation(np.repeat(np.asarray(p["sizes"], np.int64),
                                       counts))
    rng = np.random.default_rng(seed)
    starts = (rng.random(m) * (pool_rows - sizes + 1)).astype(np.int64)
    # the first request is due at 0; each later one a gap after the last
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return Schedule(due, sizes, starts)


@dataclasses.dataclass
class RequestLog:
    due: np.ndarray         # absolute perf_counter seconds
    sent: np.ndarray        # when the generator handed it over
    done: np.ndarray        # when its labels were on the host (nan: none)
    sizes: np.ndarray
    starts: np.ndarray
    labels: List[Optional[np.ndarray]]
    errors: List[str]

    def latency_ms(self) -> np.ndarray:
        """Due-to-answer latency; a request never answered is inf."""
        lat = (self.done - self.due) * 1e3
        return np.where(np.isfinite(lat), lat, np.inf)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the samples at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])


class PredictOpenLoop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.p = ctx.cell.traffic
        self.log: Optional[RequestLog] = None

    def setup(self) -> None:
        import jax
        from repro.serve import CodebookSnapshot
        cfg = self.config
        data = cfg["data"]
        gen = self.ctx.registry.data(data["generator"])
        params = data.get("params", {})
        d, k = int(cfg["d"]), int(cfg["k"])
        self.pool = np.asarray(jax.device_get(gen.make(
            self.ctx.seed, int(self.p["pool_rows"]), d, stream=1,
            **params)))
        self.C = np.asarray(jax.device_get(gen.make(
            self.ctx.seed, k, d, stream=2, **params)))
        self.snap = CodebookSnapshot.create(1, {
            "centroids": self.C, "counts": np.ones(k, np.float32),
            "n_rounds": 0, "batch_mse": float("nan")})
        for size in self.p["sizes"]:
            for _ in range(WARMUP_CALLS):
                self.snap.predict(self.pool[:size])
        self.send(WARMUP_SECONDS, self.ctx.seed + 1)

    def window(self, seconds: float, traced: bool) -> Dict[str, Any]:
        if traced:
            seconds = min(seconds, TRACE_SECONDS)
        self.log = self.send(seconds, self.ctx.seed)
        if self.log.errors:
            self.ctx.log(f"{len(self.log.errors)} requests failed; the "
                         f"first:\n{self.log.errors[0]}")
        return {"predict_p50_ms": percentile(self.log.latency_ms(), 50)}

    def send(self, seconds: float, seed: int) -> RequestLog:
        """Run the open loop for ``seconds`` of the schedule, with the
        rows ``seed`` picks; the log of every request."""
        import jax
        sch = schedule(self.p, seed, seconds, len(self.pool))
        m = len(sch.sizes)
        t0 = time.perf_counter() + 0.01
        log = RequestLog(due=t0 + sch.due_s, sent=np.full(m, np.nan),
                         done=np.full(m, np.nan), sizes=sch.sizes,
                         starts=sch.starts, labels=[None] * m, errors=[])
        handoff: "queue.Queue[int]" = queue.Queue()

        def generate():
            for i in range(m):
                wait = log.due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                log.sent[i] = time.perf_counter()
                handoff.put(i)

        gen = threading.Thread(target=generate, name="bench-generator")
        gen.start()
        give_up = t0 + seconds + GRACE_S
        try:
            for _ in range(m):
                try:
                    i = handoff.get(
                        timeout=max(0.0, give_up - time.perf_counter()))
                except queue.Empty:
                    break
                rows = self.pool[sch.starts[i]:sch.starts[i] + sch.sizes[i]]
                try:
                    with jax.profiler.TraceAnnotation("bench.predict"):
                        labels = self.snap.predict(rows)
                except Exception:
                    log.errors.append(traceback.format_exc())
                    continue
                now = time.perf_counter()
                if now <= give_up:
                    log.done[i] = now
                    log.labels[i] = labels
        finally:
            gen.join()
        return log

    @property
    def attempted(self) -> int:
        return len(self.log.sizes)

    def check(self, limits: Dict[str, float]):
        """(failed requests, {number: value}) by the reference, over the
        labels of every request answered in time. A request fails when
        its own rows break a limit."""
        ref = self.ctx.registry.reference(self.config["reference"])
        log = self.log
        answered = [i for i, lab in enumerate(log.labels) if lab is not None]
        failed = len(log.sizes) - len(answered)
        if not answered:
            return failed, {}
        sizes = log.sizes[answered]
        rows = np.concatenate([np.arange(log.starts[i],
                                         log.starts[i] + log.sizes[i])
                               for i in answered])
        # an answer of the wrong length labels none of its rows
        labels = np.concatenate([
            np.asarray(log.labels[i]).reshape(-1)
            if np.size(log.labels[i]) == log.sizes[i]
            else np.full(log.sizes[i], -1) for i in answered])
        per_row = ref.label_rows(self.pool, self.C, labels, rows=rows)
        cuts = np.cumsum(sizes)[:-1]
        for parts in zip(*(np.split(per_row[n], cuts) for n in limits)):
            nums = ref.reduce_rows(dict(zip(limits, parts)))
            failed += not all(nums[n] <= limits[n] for n in limits)
        return failed, ref.reduce_rows(per_row)


def control(driver: PredictOpenLoop) -> None:
    """Put the reference's control in the program's place: every
    answered request relabelled by nearest-centroid search at the next
    precision down."""
    ref = driver.ctx.registry.reference(driver.config["reference"])
    log = driver.log
    labels = ref.assign(driver.pool, driver.C)
    log.labels = [None if lab is None
                  else labels[log.starts[i]:log.starts[i] + log.sizes[i]]
                  for i, lab in enumerate(log.labels)]


def make(ctx) -> PredictOpenLoop:
    return PredictOpenLoop(ctx)
