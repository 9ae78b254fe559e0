"""Double-buffered chunk pipeline over a BankGrid.

The UPMEM SDK (and the faithful ``prim.*.pim()`` baselines) serialize the
three phases of every workload invocation:

    scatter | compute | retrieve | scatter | compute | retrieve | ...

Nothing in JAX forces that: ``device_put`` and bank-local phases are enqueued
asynchronously, so chunk k+1's CPU→bank scatter can be issued while chunk k's
bank-local phase is still in flight, and chunk k-1's bank→CPU copy drains
meanwhile (``copy_to_host_async``).  The steady state is the classic
three-stage software pipeline:

    scatter k+1  ─┐
    compute k     ├─ concurrent
    retrieve k-1 ─┘

``run_pipelined_many`` generalizes to a *stream* of same-workload requests:
their chunks flow through one pipeline back-to-back, so the banks never
drain between requests — that is the scheduler's batching payoff.

``run_pipelined_ranked`` adds the second level of the hierarchy
(DESIGN.md §10): on a :class:`~repro.core.banked.RankGrid` every request's
chunks are sharded across ranks in contiguous blocks and each rank drives
its own double-buffered pipeline over its own devices (one thread per rank
— JAX dispatch to disjoint device sets proceeds concurrently, the analogue
of the paper's rank-parallel CPU↔DPU transfers).  The host merges each
request's parts in global chunk order, so order-sensitive merges (SCAN's
running offset) stay correct.

Spans (``runtime/trace.py``, DESIGN.md §11), on the thread that runs the
pipeline, each tagged with the request's id: ``split`` (cache acquire and
split, including per-request broadcasts such as GEMV's ``x``),
``scatter`` / ``scatter_cached``, ``launch`` (the asynchronous enqueue of
the compute phase and of its output's copy to the host), ``device_wait``
(blocked on a chunk's output), ``copy_out`` (the host copy after it) and
``merge``.  The same intervals stamp each request's ``device_wait_s`` and
``host_self_s`` (the rest of its time inside those spans).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import TYPE_CHECKING, Any, Sequence

import jax

from repro.core.banked import BankGrid
from repro.core.transfer import tree_nbytes

from .resident import unwrap_handles
from .telemetry import RequestRecord, _phases
from .trace import NULL_SPAN, get_tracer, span, tracing

if TYPE_CHECKING:  # annotation-only: importing repro.prim pulls the suite
    from repro.prim.common import ChunkedWorkload, PhaseTimes

    from .autotune import TunedPlan


@dataclasses.dataclass
class PipelineResult:
    value: Any
    makespan: float
    phases: PhaseTimes      # host-observed buckets (see telemetry docstring)
    n_chunks: int


def _host_prefetch(outs) -> None:
    """Start async device→host copies for every array in ``outs``."""
    for leaf in jax.tree_util.tree_leaves(outs):
        try:
            leaf.copy_to_host_async()
        except AttributeError:
            pass


class _Buckets:
    """Host wall time of one request on one pipeline thread: the
    PhaseTimes buckets, the seconds inside the request's pipeline spans
    (``busy``) and, of those, blocked on its chunks (``wait``)."""

    def __init__(self):
        self.times = _phases()
        self.busy = 0.0
        self.wait = 0.0

    def add(self, phase: str | None, t0: float, wait: bool = False) -> float:
        t1 = time.perf_counter()
        if phase is not None:
            setattr(self.times, phase, getattr(self.times, phase) + (t1 - t0))
        self.busy += t1 - t0
        if wait:
            self.wait += t1 - t0
        return t1


def _req_ids(records, n_req: int) -> list:
    """Span tags: each request's telemetry id when records ride along,
    else its batch-local index."""
    if records is None:
        return list(range(n_req))
    return [rec.request_id for rec in records]


def _stamp_plan(records, plan) -> None:
    """A tuned plan's promises, on every record of the batch."""
    stage_pred = dict(getattr(plan, "predicted_stage_s", {}) or {})
    for rec in records:
        rec.tuned = True
        rec.predicted_overlap = plan.predicted_overlap
        if stage_pred:
            rec.predicted_stage_s = dict(stage_pred)


def _stamp_split(rec, n_chunks: int, hit: bool, plan) -> None:
    rec.n_chunks = n_chunks
    rec.cache_hit = hit
    if hit and plan is not None and getattr(plan, "warm_predicted_overlap",
                                            0.0):
        rec.predicted_overlap = plan.warm_predicted_overlap


def _meta_cached_span(workload, ent, hit: bool, req):
    """A meta-resident hit (BS): the skipped broadcast happens at split
    time, so its ``scatter_cached`` span wraps the split, not a chunk."""
    if ent is None or not hit or ent.chunk_resident:
        return NULL_SPAN
    return span("scatter_cached", "cpu_dpu", req=req,
                workload=workload.name, bytes=ent.nbytes,
                fingerprint=ent.fingerprint)


def _scatter_chunk(view, workload, meta, ent, gidx, chunk, args, total,
                   req):
    """Push one chunk to the banks, or serve it from the request's
    resident entry (DESIGN.md §12).  The push is exactly-once: the entry
    lock is held across it, so a second filler of the same fingerprint can
    only observe the stored buffers, never race the push."""
    name = workload.name
    if ent is None or not ent.chunk_resident:
        with span("scatter", "cpu_dpu", req=req, chunk=gidx, workload=name,
                  bytes=tree_nbytes(chunk) if tracing() else None):
            return workload.scatter(view, meta, chunk)
    with ent.lock:
        bufs = ent.get(gidx)
        if bufs is not None:
            with span("scatter_cached", "cpu_dpu", req=req, chunk=gidx,
                      workload=name,
                      bytes=ent.nbytes // max(1, ent.expected_chunks),
                      fingerprint=ent.fingerprint):
                return bufs
        if chunk is None:                # placeholder outlived the entry
            chunk = _refill_chunk(view, workload, args, total, gidx)
        with span("scatter", "cpu_dpu", req=req, chunk=gidx, workload=name,
                  bytes=tree_nbytes(chunk) if tracing() else None):
            bufs = workload.scatter(view, meta, chunk)
        ent.store(gidx, bufs)
        return bufs


def _launch_chunk(view, workload, meta, bufs, req, gidx, bucket):
    """Enqueue one chunk's compute phase and the copy of its output to the
    host; neither waits for the device."""
    ts = time.perf_counter()
    with span("launch", "dpu", req=req, chunk=gidx, workload=workload.name):
        outs = workload.compute(view, meta, bufs)
        _host_prefetch(outs)
    bucket.add("dpu", ts)
    return outs


def _retire_chunk(view, workload, meta, outs, req, gidx, bucket):
    """Block for one chunk's output, then copy it to the host; returns the
    host part and the time it landed."""
    ts = time.perf_counter()
    with span("device_wait", "dpu_cpu", req=req, chunk=gidx,
              workload=workload.name):
        jax.block_until_ready(outs)
    t1 = bucket.add("dpu_cpu", ts, wait=True)
    with span("copy_out", "dpu_cpu", req=req, chunk=gidx,
              workload=workload.name):
        part = workload.retrieve(view, meta, outs)
    return part, bucket.add("dpu_cpu", t1)


def _effective_chunks(workload, n_chunks, plan, cache) -> tuple[int, bool]:
    """Resolve the pipeline depth and whether the resident cache is in play.

    A plan overrides ``n_chunks``; when the cache applies and the plan
    carries a warm solve, the *warm* depth wins for cold fills too — the
    fingerprint bakes in the chunk count (placement spec), so fill and hit
    must agree on one depth for the fill to ever be reused."""
    use_cache = cache is not None and workload.supports_residency
    if plan is not None:
        n_chunks = plan.n_chunks
        if use_cache and getattr(plan, "warm_n_chunks", 0):
            n_chunks = plan.warm_n_chunks
    return n_chunks, use_cache


def _refill_chunk(view, workload, args, total, gidx):
    """Recompute one resident chunk whose warm-hit ``None`` placeholder
    outlived its entry (the cache was cleared/released mid-flight — the
    in-flight lease makes eviction impossible, so this is a last-resort
    self-heal, not a hot path): re-run the resident split and hand back
    the real chunk so the request degrades to a plain scatter."""
    res = tuple(unwrap_handles(args)[j] for j in workload.resident_args)
    _, res_chunks = workload.split_resident(view, total, *res)
    return res_chunks[gidx]


def _split_with_cache(view, workload, args, total, ent, rank=0, hit=False):
    """Split one request against a resident entry (or plainly when
    ``ent`` is None).  Returns (meta, chunks) where chunks are ``None``
    placeholders only on a warm **hit** — their device buffers already live
    in the ready entry.  On a miss the real chunk list is always produced,
    even when another request already installed the rank meta (a second
    filler of the same fingerprint, or a retry after a failed fill, must be
    able to push the buffers the entry is still missing; already-stored
    chunks are deduplicated under the entry lock at scatter time)."""
    args = unwrap_handles(args)           # workloads never see the token
    if ent is None:
        return workload.split(view, total, *args)
    res = tuple(args[j] for j in workload.resident_args)
    rm = ent.rank_meta(rank)
    res_chunks = None
    if rm is None:
        rm0, res_chunks = workload.split_resident(view, total, *res)
        rm = ent.set_rank_meta(rank, rm0,
                               n_chunks=len(res_chunks or ()))
    meta, var_chunks = workload.split_varying(view, total, rm, *args)
    if ent.chunk_resident:
        if hit:
            chunks = [None] * ent.expected_chunks
        elif res_chunks is None:
            _, res_chunks = workload.split_resident(view, total, *res)
            chunks = res_chunks
        else:
            chunks = res_chunks
    else:
        chunks = var_chunks
    return meta, chunks


def run_pipelined(grid: BankGrid, workload: ChunkedWorkload, *args,
                  n_chunks: int = 4, plan: TunedPlan | None = None,
                  record: RequestRecord | None = None,
                  cache=None) -> PipelineResult:
    """Run one request through the chunk pipeline; returns PipelineResult.
    A :class:`~repro.runtime.autotune.TunedPlan` overrides ``n_chunks``;
    a :class:`~repro.runtime.resident.ResidentCache` serves warm scatters."""
    n_chunks, _ = _effective_chunks(workload, n_chunks, plan, cache)
    records = [record] if record is not None else None
    results, makespans, phases = run_pipelined_many(
        grid, workload, [args], n_chunks=n_chunks, plan=plan,
        records=records, cache=cache, _full=True)
    return PipelineResult(results[0], makespans[0], phases[0], n_chunks)


def run_pipelined_many(grid: BankGrid, workload: ChunkedWorkload,
                       requests: Sequence[tuple], n_chunks: int = 4,
                       plan: TunedPlan | None = None,
                       records: Sequence[RequestRecord] | None = None,
                       cache=None, _full: bool = False):
    """Stream every request's chunks through one double-buffered pipeline.

    ``requests`` is a sequence of argument tuples for ``workload``.  Returns
    the list of results (plus per-request makespans and phase buckets when
    ``_full``).  Requests complete in submission order; each is split just
    before its first chunk is scattered (its host work overlaps the chunks
    already in flight), and its result is merged as soon as its last chunk
    retires, while later requests' chunks are already in flight.  A
    request's service starts at its split.  A
    :class:`~repro.runtime.autotune.TunedPlan` overrides ``n_chunks`` and
    stamps its predicted overlap on the records; a
    :class:`~repro.runtime.resident.ResidentCache` lets requests whose
    resident operand is already placed skip the scatter stage (DESIGN.md
    §12) — served chunks emit ``scatter_cached`` spans instead of pushes.
    """
    n_chunks, use_cache = _effective_chunks(workload, n_chunks, plan, cache)
    if plan is not None and records is not None:
        _stamp_plan(records, plan)
    n_req = len(requests)
    rids = _req_ids(records, n_req)
    metas: list = [None] * n_req
    entries: list = [None] * n_req        # ResidentEntry per request
    flat: list = []                       # (req_idx, chunk_idx, chunk)
    bucket = [_Buckets() for _ in range(n_req)]
    t_start = [0.0] * n_req
    t_done = [0.0] * n_req
    parts: list = [[] for _ in range(n_req)]
    chunk_count = [0] * n_req
    results: list = [None] * n_req
    unsplit = iter(range(n_req))

    def split_next() -> bool:
        """Split the next request onto the chunk stream; False when every
        request is split."""
        i = next(unsplit, None)
        if i is None:
            return False
        ts = t_start[i] = time.perf_counter()
        with span("split", "split", req=rids[i], workload=workload.name):
            ent, hit = (cache.acquire(workload, requests[i],
                                      (grid.n_banks, 1, n_chunks))
                        if use_cache else (None, False))
            entries[i] = ent
            with _meta_cached_span(workload, ent, hit, rids[i]):
                metas[i], chunks = _split_with_cache(
                    grid, workload, requests[i], n_chunks, ent, hit=hit)
        bucket[i].add(None, ts)
        chunk_count[i] = len(chunks)
        flat.extend((i, ci, c) for ci, c in enumerate(chunks))
        if records is not None:
            _stamp_split(records[i], len(chunks), hit, plan)
        return True

    def ready(k) -> bool:
        """Whether chunk ``k`` of the stream exists, splitting requests
        until it does."""
        while len(flat) <= k and split_next():
            pass
        return k < len(flat)

    def scatter(k):
        i, ci, chunk = flat[k]
        ts = time.perf_counter()
        bufs = _scatter_chunk(grid, workload, metas[i], entries[i], ci,
                              chunk, requests[i], n_chunks, rids[i])
        bucket[i].add("cpu_dpu", ts)
        return bufs

    def retire(entry):
        """Block for one in-flight chunk and fold it into its request."""
        i, ci, outs = entry
        part, t1 = _retire_chunk(grid, workload, metas[i], outs, rids[i],
                                 ci, bucket[i])
        parts[i].append(part)
        if len(parts[i]) == chunk_count[i]:
            with span("merge", "inter_dpu", req=rids[i],
                      workload=workload.name):
                results[i] = workload.merge(grid, metas[i], parts[i])
            t_done[i] = bucket[i].add("inter_dpu", t1)

    t0 = time.perf_counter()
    try:
        in_flight: list = []
        bufs = scatter(0) if ready(0) else None
        k = 0
        while k < len(flat):
            i, ci, _ = flat[k]
            outs = _launch_chunk(grid, workload, metas[i], bufs, rids[i], ci,
                                 bucket[i])
            if ready(k + 1):
                bufs = scatter(k + 1)    # overlaps compute of chunk k
            in_flight.append((i, ci, outs))
            if len(in_flight) > 1:       # retire k-1 while k computes
                retire(in_flight.pop(0))
            k += 1
        while in_flight:
            retire(in_flight.pop(0))
    finally:
        # retire every acquire() lease — including on error paths, or the
        # entries would be unevictable forever
        if use_cache:
            for ent in entries:
                cache.release(ent)

    makespans = [t_done[i] - (t_start[i] or t0) for i in range(n_req)]
    if records is not None:
        for i, rec in enumerate(records):
            rec.t_start = t_start[i] or t0
            rec.t_finish = t_done[i]
            rec.phases = bucket[i].times
            rec.device_wait_s = bucket[i].wait
            rec.host_self_s = bucket[i].busy - bucket[i].wait
    if _full:
        return results, makespans, [b.times for b in bucket]
    return results


# ---------------------------------------------------------------------------
# rank-parallel pipelines (DESIGN.md §10)
# ---------------------------------------------------------------------------

def _resolve_ranks(grid, n_ranks, plan) -> int:
    """Effective rank count.  An explicit caller ``n_ranks`` wins — that is
    how the scheduler's elastic allocator (DESIGN.md §13) and the
    autotuner's rank probes override placement per batch.  Otherwise the
    plan's measured pick applies (a probed plan is authoritative even when
    it adopted 1 — flat measured best), else every rank the grid has —
    always clamped to the hardware."""
    have = getattr(grid, "n_ranks", 1)
    want = n_ranks
    if want is None and plan is not None:
        probed = bool(getattr(plan, "rank_measured_s", None))
        if probed or getattr(plan, "n_ranks", 1) > 1:
            want = plan.n_ranks
    if want is None:
        want = have
    return max(1, min(want, have))


def _rank_worker(view, workload, metas, stream, bucket, t_start, t_retired,
                 entries, requests, split_total, rids):
    """One rank's double-buffered pipeline over its assigned chunk stream.

    ``stream`` is an ordered list of (req_idx, global_chunk_idx, chunk);
    returns {req_idx: [(global_chunk_idx, part), ...]} and stamps
    ``t_retired[i]`` with the wall time this rank retired request i's last
    chunk.  Same three-stage loop as :func:`run_pipelined_many`, minus the
    merge — parts go back to the caller, which merges across ranks in
    global chunk order.  ``entries`` carries per-request resident-cache
    entries (DESIGN.md §12): chunks whose buffers already live in the
    entry are served instead of pushed, under the entry lock so disjoint
    rank blocks and repeated fills stay exactly-once.  Spans land on this
    rank's own track: the caller sets the tracer's thread-local track
    override to ``rank-r`` (DESIGN.md §11), so a traced run shows one
    pipeline lane per rank."""
    parts: dict[int, list] = {}
    if not stream:
        return parts

    def scatter(k):
        i, gidx, chunk = stream[k]
        if not t_start[i]:
            t_start[i] = time.perf_counter()
        ts = time.perf_counter()
        bufs = _scatter_chunk(view, workload, metas[i], entries[i], gidx,
                              chunk, requests[i], split_total, rids[i])
        bucket[i].add("cpu_dpu", ts)
        return bufs

    def retire(entry):
        i, gidx, outs = entry
        part, t_retired[i] = _retire_chunk(view, workload, metas[i], outs,
                                           rids[i], gidx, bucket[i])
        parts.setdefault(i, []).append((gidx, part))

    in_flight: list = []
    bufs = scatter(0)
    for k in range(len(stream)):
        i, gidx = stream[k][0], stream[k][1]
        outs = _launch_chunk(view, workload, metas[i], bufs, rids[i], gidx,
                             bucket[i])
        if k + 1 < len(stream):
            bufs = scatter(k + 1)        # overlaps compute of chunk k
        in_flight.append((i, gidx, outs))
        if len(in_flight) > 1:
            retire(in_flight.pop(0))
    while in_flight:
        retire(in_flight.pop(0))
    return parts


def run_pipelined_ranked(grid, workload: ChunkedWorkload,
                         requests: Sequence[tuple], n_chunks: int = 4,
                         n_ranks: int | None = None,
                         plan: TunedPlan | None = None,
                         records: Sequence[RequestRecord] | None = None,
                         cache=None, _full: bool = False):
    """Rank-parallel chunk pipelines over a RankGrid (DESIGN.md §10).

    Every request is split into ``n_ranks * n_chunks`` equal chunks sized
    for one rank's banks; rank r owns the r-th contiguous block and streams
    it through its own double-buffered pipeline on its own devices (thread
    per rank).  Per-bank work matches the flat pipeline at the same
    ``n_chunks`` — a rank's chunk spans ``banks_per_rank`` banks instead of
    all of them — while transfers and compute for different ranks overlap,
    modeling the paper's ~×ranks rank-parallel CPU↔DPU bandwidth.

    Degenerates to :func:`run_pipelined_many` on the flat view when one
    rank is in play, so ``ranks=1`` sessions behave exactly as before.  A
    :class:`~repro.runtime.autotune.TunedPlan` overrides both ``n_chunks``
    and (when tuned with a rank dimension) ``n_ranks``.
    """
    n_ranks = _resolve_ranks(grid, n_ranks, plan)
    n_chunks, use_cache = _effective_chunks(workload, n_chunks, plan, cache)
    if n_ranks <= 1:
        return run_pipelined_many(grid, workload, requests,
                                  n_chunks=n_chunks, plan=plan,
                                  records=records, cache=cache, _full=_full)
    if records is not None and plan is not None:
        _stamp_plan(records, plan)

    rep = grid.rank_view(0)          # all views share the per-rank geometry
    n_req = len(requests)
    rids = _req_ids(records, n_req)
    # every rank splits with its *own* view: split is deterministic host
    # work (identical chunks), but several workloads broadcast per-request
    # constants to the devices at split time (GEMV's x, BS's array, ...) —
    # each rank needs those constants on its own banks
    metas = [[None] * n_req for _ in range(n_ranks)]
    entries: list = [None] * n_req
    streams: list[list] = [[] for _ in range(n_ranks)]
    # bucket[0] is this thread's: rank 0's pipeline, and the splits and
    # merges, which stamp the records' device_wait_s and host_self_s
    bucket = [[_Buckets() for _ in range(n_req)] for _ in range(n_ranks)]
    t_first = [[0.0] * n_req for _ in range(n_ranks)]
    t_retired = [[0.0] * n_req for _ in range(n_ranks)]

    t0 = time.perf_counter()
    total = n_ranks * n_chunks
    results: list = [None] * n_req
    rank_parts: list = [None] * n_ranks
    errors: list = [None] * n_ranks

    tr = get_tracer()

    def worker(r):
        try:
            # one trace track per rank pipeline (rank 0 runs on the caller's
            # thread, so the thread name alone cannot identify its track)
            with tr.track(f"rank-{r}"):
                rank_parts[r] = _rank_worker(grid.rank_view(r), workload,
                                             metas[r], streams[r], bucket[r],
                                             t_first[r], t_retired[r],
                                             entries, requests, total, rids)
        except BaseException as e:           # noqa: BLE001 — re-raised below
            errors[r] = e

    try:
        for i, args in enumerate(requests):
            per = n_chunks
            ts = time.perf_counter()
            with span("split", "split", req=rids[i], workload=workload.name):
                ent, hit = (cache.acquire(workload, args,
                                          (grid.n_banks, n_ranks, total))
                            if use_cache else (None, False))
                entries[i] = ent
                with _meta_cached_span(workload, ent, hit, rids[i]):
                    for r in range(n_ranks):
                        metas[r][i], chunks = _split_with_cache(
                            grid.rank_view(r), workload, args, total, ent,
                            rank=r, hit=hit)
                        per = -(-len(chunks) // n_ranks)  # contiguous blocks
                        streams[r].extend(
                            (i, g, chunks[g])
                            for g in range(r * per,
                                           min((r + 1) * per, len(chunks))))
            bucket[0][i].add(None, ts)
            if records is not None:
                # n_chunks is the per-pipeline depth (matches the flat path
                # and the plan's value); total chunks = n_chunks * n_ranks
                _stamp_split(records[i], per, hit, plan)
                records[i].n_ranks = n_ranks

        threads = [threading.Thread(target=worker, args=(r,),
                                    name=f"pim-rank-{r}", daemon=True)
                   for r in range(1, n_ranks)]
        for t in threads:
            t.start()
        worker(0)                            # rank 0 runs on this thread
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
    finally:
        # retire every acquire() lease — including on error paths, or the
        # entries would be unevictable forever
        if use_cache:
            for ent in entries:
                cache.release(ent)

    makespans = [0.0] * n_req
    phases = []
    for i in range(n_req):
        parts = sorted(p for ps in rank_parts for p in ps.get(i, ()))
        ts = time.perf_counter()
        with span("merge", "inter_dpu", req=rids[i], workload=workload.name):
            results[i] = workload.merge(rep, metas[0][i],
                                        [p for _, p in parts])
        merge_dt = bucket[0][i].add(None, ts) - ts
        times = _phases()
        for r in range(n_ranks):                 # host-observed, summed over
            for k in dataclasses.fields(times):  # the rank threads
                setattr(times, k.name, getattr(times, k.name)
                        + getattr(bucket[r][i].times, k.name))
        times.inter_dpu += merge_dt
        phases.append(times)
        started = [t_first[r][i] for r in range(n_ranks) if t_first[r][i]]
        t_start = min(started) if started else t0
        # a request completes when its last chunk retires on the slowest
        # rank, plus its merge; merges themselves are deferred to the join,
        # so stamping merge wall time here would bill early requests in a
        # batch for the whole stream's tail (the flat path merges eagerly)
        retired = max(t_retired[r][i] for r in range(n_ranks))
        t_done = (retired or time.perf_counter()) + merge_dt
        makespans[i] = t_done - t_start
        if records is not None:
            own = bucket[0][i]
            records[i].t_start = t_start
            records[i].t_finish = t_done
            records[i].phases = times
            records[i].device_wait_s = own.wait
            records[i].host_self_s = own.busy - own.wait
    if _full:
        return results, makespans, phases
    return results
