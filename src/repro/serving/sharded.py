"""Sharded multi-replica edge/cloud serving — data parallelism over the
mesh's "data" axis, with the cloud flush overlapped against the next
edge batch.

`serve_stream_batched` (batched.py) amortizes Python dispatch over
micro-batches but still runs on one replica and blocks on every cloud
flush. This module scales the same pipeline out and overlaps it:

  * **data-parallel edge/cloud launches** — every depth-bucketed
    pow2-padded launch (edge buckets and offload-queue cloud flushes)
    is placed with a ``NamedSharding`` that splits its row axis over the
    mesh's "data" axis (`launch/shardings.py:sanitize_spec` guards
    divisibility; bucket caps are rounded up to a multiple of `replicas`
    — see `batched._bucket_cap` — so they always divide). Model parameters are placed by
    `sharding/rules.py:param_specs` — fully replicated on the 1-D
    serving mesh, Megatron-split if a caller hands a mesh with a
    "model" axis.
  * **per-replica bandit statistics** — each replica owns a contiguous
    shard of the micro-batch. Its arms are its slice of the global
    frozen-state selection (`choose_splits` is round-robin-then-argmax
    from the state frozen at the batch boundary, so slicing is exactly
    per-replica selection with zero communication), and its update
    statistics are summarized by `SplitEEController.prepare_shard_update`
    and folded into the global state by `merge_shard_updates` at the
    batch boundary — the host-side all-reduce. The fold replays the
    sequential arithmetic, so replica count does NOT change the policy:
    R shards merge bit-identically to the unsharded batch update.
  * **async offload (depth-K pipeline)** — with ``overlap=True`` the
    batched `cloud_fn` flush for batch t is *dispatched*
    (`OffloadQueue.flush_async`, no block) and resolved only after up to
    ``overlap_depth`` later batches have selected their arms and
    launched their edge buckets. The queue keeps a ring of in-flight
    `PendingFlush` slots, so up to K cloud flushes proceed concurrently
    with edge work. Feedback for batch t therefore lands K batches later
    than in the synchronous path: delay grows from at most B-1 rounds to
    at most (K+1)·B-1 (asserted at every fold) — still the
    additive-regret delayed-feedback regime (Joulani et al., 2013).
    ``overlap_depth=1`` is classic double buffering. The result dict
    records the pipeline under ``"overlap"``.

Semantics: with ``replicas=1`` and ``overlap=False`` this path is
**bit-identical** to `serve_stream_batched` (pinned by the differential
test in tests/test_serving_sharded.py). Overlap changes *when* updates
land (K batches later); replicas change only *where* compute runs.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.controller import SplitEEController
from repro.core.rewards import CostModel
from repro.data.stream import microbatches
from repro.launch.mesh import make_serving_mesh
from repro.launch.shardings import param_shardings, sanitize_spec
from repro.serving.batched import OffloadQueue, _offload_scale
from repro.serving.offload_codec import OffloadCodec
from repro.serving.simulator import EdgeCloudRuntime
from repro.serving.tracing import Tracer


def _shard_sizes(total: int, replicas: int) -> List[int]:
    """Contiguous per-replica shard sizes (first shards take the tail)."""
    base, rem = divmod(total, replicas)
    return [base + (1 if r < rem else 0) for r in range(replicas)]


def _data_put(mesh: Mesh):
    """device_put closure splitting an array's leading axis over "data"."""
    def put(arr):
        spec = P("data", *([None] * (np.ndim(arr) - 1)))
        return jax.device_put(
            arr, NamedSharding(mesh, sanitize_spec(mesh, spec, arr.shape)))
    return put


@dataclasses.dataclass
class _BatchCtx:
    """Everything finalization needs once the cloud flush resolves."""
    arms: np.ndarray
    conf_paths: List[Optional[np.ndarray]]
    batch_preds: List[int]
    labels: List[Optional[int]]
    seq_len: int
    pending: Any                      # PendingFlush
    start: int = 0                    # global round index of first sample
    overlapped: bool = False
    members: Optional[List[int]] = None   # FT: hosts this batch sliced over


class _PipelineDriver:
    """The depth-K serving schedule shared by the sharded and distributed
    runtimes, incremental form: ``process_batch(batch, start)`` selects
    arms and dispatches one micro-batch's edge work + cloud flush
    (returning its _BatchCtx), up to ``overlap_depth`` contexts stay in
    flight, and ``finalize`` folds them FIFO. Asserts the feedback-delay
    bound <= (K+1)*B - 1 at every fold.

    ``push`` serves one micro-batch; ``drain`` folds the remaining ring.
    The offline entry points wrap this in `_drive_pipeline`; the
    push-mode `api.Engine` drives it one submit at a time — same object,
    same schedule, which is what makes the two bit-identical.

    The in-flight bound is enforced at two cooperating levels with the
    same K: this deque bounds *fold order* (controller updates land
    FIFO), while the queue's ``flush_async(depth=K)`` ring bounds the
    *device work itself* — a backstop that holds even for callers that
    defer resolution indefinitely. Both resolve the same PendingFlush
    objects FIFO and ``resolve`` is idempotent, so whichever fires first
    the results are identical; only where blocking happens shifts.
    """

    def __init__(self, *, batch_size: int, overlap: bool,
                 overlap_depth: int, process_batch, finalize):
        self.batch_size = batch_size
        self.overlap = overlap
        self.overlap_depth = overlap_depth
        self.process_batch = process_batch
        self.finalize = finalize
        self.inflight: collections.deque[_BatchCtx] = collections.deque()
        self.selected = 0              # arms drawn so far (global rounds)
        self.batches = 0

    def _fold(self, ctx: _BatchCtx):
        # feedback-delay bound: the oldest sample of this batch has seen
        # at most (K+1)*B - 1 later selections before its update lands.
        depth_eff = self.overlap_depth if self.overlap else 0
        bound = (depth_eff + 1) * self.batch_size - 1
        assert self.selected - 1 - ctx.start <= bound, (
            f"feedback delay {self.selected - 1 - ctx.start} exceeds "
            f"(K+1)*B-1 = {bound}")
        self.finalize(ctx)

    def push(self, batch):
        ctx = self.process_batch(batch, self.selected)
        self.selected += len(batch)
        self.batches += 1
        if self.overlap:
            # depth-K pipeline: cloud launches from the last up-to-K
            # batches stay in flight behind this batch's edge phase;
            # once the ring is full the oldest resolves and folds.
            self.inflight.append(ctx)
            while len(self.inflight) > self.overlap_depth:
                oldest = self.inflight.popleft()
                oldest.overlapped = True
                self._fold(oldest)
        else:
            self._fold(ctx)

    def drain(self):
        while self.inflight:           # final drain, FIFO
            ctx = self.inflight.popleft()
            # all but the last in-flight batch had later edge work
            # dispatched behind them
            ctx.overlapped = bool(self.inflight)
            self._fold(ctx)


def _drive_pipeline(stream, *, batch_size: int, max_samples: int,
                    overlap: bool, overlap_depth: int,
                    process_batch, finalize) -> int:
    """Offline driver: replay a finite stream through a `_PipelineDriver`.
    Returns the batch count."""
    driver = _PipelineDriver(batch_size=batch_size, overlap=overlap,
                             overlap_depth=overlap_depth,
                             process_batch=process_batch,
                             finalize=finalize)
    for batch in microbatches(stream, batch_size, max_samples):
        driver.push(batch)
    driver.drain()
    return driver.batches


def _resolve_cloud(ctx: _BatchCtx):
    """Resolve ctx's cloud flush: patch cloud predictions into
    ``ctx.batch_preds`` and return (conf_Ls, offload_bytes) per slot.

    Bytes come from the flush's own measured payload
    (``PendingFlush.slot_bytes``, recorded at dispatch), not re-derived
    from the config dtype — so accounting cannot drift from what was
    actually transmitted (it used to charge
    ``runtime.offload_bytes(1, seq_len)`` regardless of the payload)."""
    size = len(ctx.arms)
    cloud = ctx.pending.resolve()
    conf_Ls: List[Optional[float]] = [None] * size
    obs = [0] * size
    for s, (c_L, p_L) in cloud.items():
        conf_Ls[s] = c_L
        ctx.batch_preds[s] = p_L
        obs[s] = ctx.pending.slot_bytes[s]
    return conf_Ls, obs


def _serve_result(ctl: SplitEEController, *, n: int, batch_size: int,
                  replicas: int, preds, correct, overlap: bool,
                  overlap_depth: int, batches: int,
                  overlapped: int) -> Dict[str, Any]:
    """Result dict shared by the sharded and distributed runtimes."""
    hist = {k: np.asarray(v) for k, v in ctl.history.items()}
    tot = ctl.totals
    out = {
        "n": n,
        "batch_size": batch_size,
        "replicas": replicas,
        "preds": np.asarray(preds),
        # scalar accounting comes from the controller's O(1) aggregates
        # so it survives record_history=False
        "cost_total": float(tot["cost"]),
        "offload_frac": (1.0 - tot["exited"] / tot["served"]
                         if tot["served"] else 0.0),
        "offload_bytes": int(tot["offload_bytes"]),
        "arms": hist["arm"],
        "rewards": hist["reward"],
        "exited": hist["exited"],
        "overlap": {"enabled": overlap, "depth": overlap_depth,
                    "batches": batches, "batches_overlapped": overlapped},
        "state": ctl.snapshot(),
    }
    if correct:
        out["accuracy"] = float(np.mean(correct))
    return out


class _ShardedSession:
    """Incremental driver of the sharded micro-batch schedule.

    Owns the mesh placement, controller, offload queue, and the depth-K
    `_PipelineDriver`; one `push(batch)` runs exactly one round of the
    offline loop, so the one-shot `_serve_stream_sharded` and the
    push-mode `api.Engine` are the same machinery by construction.

    Serving semantics (what ``replicas``/``overlap``/``overlap_depth``
    mean, and the bit-identity ladder back to the batched path) are
    documented in the module docstring above.
    """

    def __init__(self, runtime: EdgeCloudRuntime, params, cost: CostModel,
                 *, batch_size: int = 32, replicas: int = 1,
                 mesh: Optional[Mesh] = None, overlap: bool = True,
                 overlap_depth: int = 1, side_info: bool = False,
                 beta: float = 1.0, labels_for_accounting: bool = True,
                 record_trace: bool = False, edge_mode: str = "bucketed",
                 controller_kwargs: Optional[Dict[str, Any]] = None,
                 codec: Optional[OffloadCodec] = None):
        from repro.serving.scan_edge import select_edge_phase
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if overlap_depth < 1:
            raise ValueError(
                f"overlap_depth must be >= 1, got {overlap_depth}")
        if mesh is None:
            mesh = make_serving_mesh(replicas)
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"mesh needs a 'data' axis, got {mesh.axis_names}")
        if replicas > mesh.shape["data"]:
            raise ValueError(f"replicas={replicas} exceeds data axis "
                             f"size {mesh.shape['data']}")

        self.runtime = runtime
        self.cost = cost
        self.batch_size = batch_size
        self.replicas = replicas
        self.overlap = overlap
        self.overlap_depth = overlap_depth
        self.side_info = side_info
        self.labels_for_accounting = labels_for_accounting
        self.edge_mode = edge_mode
        self._edge_phase = select_edge_phase(edge_mode)

        self.put = _data_put(mesh)
        amap = {"model": "model" if "model" in mesh.axis_names else None,
                "fsdp": None}
        self.params = jax.device_put(
            params, param_shardings(mesh, params, axis_map=amap))

        self.ctl = SplitEEController(cost, beta=beta, side_info=side_info,
                                     **(controller_kwargs or {}))
        self.codec = codec
        self.queue = OffloadQueue(runtime, self.params, put=self.put,
                                  codec=codec)
        self.correct: List[int] = []
        self.preds: List[int] = []
        self.trace: Optional[Dict[str, list]] = (
            {"conf_path": [], "conf_L": []} if record_trace else None)
        self.n = 0
        self.overlapped = 0
        self.batch_sizes: List[int] = []   # fill levels of pushed batches
        # holds the Engine's scheduler spans; the session records none
        self.tracer = Tracer()
        self._driver = _PipelineDriver(
            batch_size=batch_size, overlap=overlap,
            overlap_depth=overlap_depth,
            process_batch=self._process_batch, finalize=self._finalize)

    def _process_batch(self, batch, start: int) -> _BatchCtx:
        """Select arms, launch the batch's edge buckets, dispatch flush."""
        B = len(batch)
        arms = self.ctl.choose_splits(B)
        tokens = np.stack([np.asarray(s["tokens"]) for s in batch])

        # ---- edge: data-parallel bucket launches, or one masked scan ---
        conf_paths, batch_preds = self._edge_phase(
            self.runtime, self.params, tokens, arms, self.cost, self.queue,
            side_info=self.side_info, put=self.put, replicas=self.replicas)

        # ---- cloud: dispatch the flush; resolve now or K batches later -
        pending = self.queue.flush_async(
            min_rows=self.replicas,
            depth=self.overlap_depth if self.overlap else None)
        labels = [int(s["labels"]) if "labels" in s else None
                  for s in batch]
        return _BatchCtx(arms=arms, conf_paths=conf_paths,
                         batch_preds=batch_preds, labels=labels,
                         seq_len=tokens.shape[1], pending=pending,
                         start=start)

    def _finalize(self, ctx: _BatchCtx):
        """Resolve the cloud flush, merge per-replica stats, book results."""
        B = len(ctx.arms)
        conf_Ls, obs = _resolve_cloud(ctx)
        scale = _offload_scale(self.codec, self.runtime, ctx.seq_len)
        # per-replica shard summaries, merged at the batch boundary
        shards = []
        lo = 0
        for size in _shard_sizes(B, self.replicas):
            hi = lo + size
            if size:
                # ctx.start is the batch's global stream position — with
                # overlap the fold runs behind selection, so the
                # controller's own round counter would lag the trace
                shards.append(self.ctl.prepare_shard_update(
                    ctx.arms[lo:hi], ctx.conf_paths[lo:hi],
                    conf_Ls[lo:hi], obs[lo:hi], round=ctx.start,
                    offload_scale=scale))
            lo = hi
        self.ctl.merge_shard_updates(shards)
        self.preds.extend(ctx.batch_preds)
        if self.trace is not None:
            self.trace["conf_path"].extend(ctx.conf_paths)
            self.trace["conf_L"].extend(conf_Ls)
        if self.labels_for_accounting:
            for s in range(B):
                if ctx.labels[s] is not None:
                    self.correct.append(
                        int(ctx.batch_preds[s] == ctx.labels[s]))
        if ctx.overlapped:
            self.overlapped += 1
        self.n += B

    def push(self, batch):
        """Serve one micro-batch (any size >= 1; ragged tails included).
        An empty push is a no-op — a scheduler tick or drain that formed
        nothing must not spend a bandit round."""
        if not batch:
            return
        self.batch_sizes.append(len(batch))
        self._driver.push(batch)

    def drain(self):
        """Resolve and fold every in-flight overlapped cloud flush."""
        self._driver.drain()

    def result(self) -> Dict[str, Any]:
        out = _serve_result(self.ctl, n=self.n, batch_size=self.batch_size,
                            replicas=self.replicas, preds=self.preds,
                            correct=self.correct, overlap=self.overlap,
                            overlap_depth=self.overlap_depth,
                            batches=self._driver.batches,
                            overlapped=self.overlapped)
        out["telemetry"] = self.tracer.snapshot()
        if self.trace is not None:
            out["trace"] = self.trace
        return out


def _serve_stream_sharded(runtime: EdgeCloudRuntime, params, stream,
                          cost: CostModel, *, batch_size: int = 32,
                          replicas: int = 1, mesh: Optional[Mesh] = None,
                          overlap: bool = True, overlap_depth: int = 1,
                          side_info: bool = False,
                          beta: float = 1.0, max_samples: int = 0,
                          labels_for_accounting: bool = True,
                          record_trace: bool = False,
                          edge_mode: str = "bucketed",
                          controller_kwargs: Optional[Dict[str, Any]] = None,
                          codec: Optional[OffloadCodec] = None,
                          ) -> Dict[str, Any]:
    """Offline driver: replay a finite stream through a sharded session.

    Same contract as `_serve_stream_batched`, plus:

    ``replicas``  data-parallel replica count (must fit the mesh's
                  "data" axis; a 1-D mesh over the first `replicas`
                  devices is built when ``mesh`` is None).
    ``mesh``      explicit mesh with a "data" axis (and optionally a
                  "model" axis, which param placement honors).
    ``overlap``   pipeline the offload queue: batch t's cloud flush is
                  resolved only after up to ``overlap_depth`` later
                  batches have dispatched their edge work. Off: cloud
                  resolves at t's own boundary, reproducing the
                  synchronous batched semantics.
    ``overlap_depth``  max in-flight cloud flushes K (>= 1). K=1 is
                  double buffering; larger K hides longer cloud
                  latencies at the price of feedback delayed by up to
                  (K+1)*B-1 rounds (asserted at every fold).
    """
    sess = _ShardedSession(runtime, params, cost, batch_size=batch_size,
                           replicas=replicas, mesh=mesh, overlap=overlap,
                           overlap_depth=overlap_depth, side_info=side_info,
                           beta=beta,
                           labels_for_accounting=labels_for_accounting,
                           record_trace=record_trace, edge_mode=edge_mode,
                           controller_kwargs=controller_kwargs, codec=codec)
    for batch in microbatches(stream, batch_size, max_samples):
        sess.push(batch)
    sess.drain()
    return sess.result()


def serve_stream_sharded(runtime: EdgeCloudRuntime, params, stream,
                         cost: CostModel, *, batch_size: int = 32,
                         replicas: int = 1, mesh: Optional[Mesh] = None,
                         overlap: bool = True, overlap_depth: int = 1,
                         side_info: bool = False,
                         beta: float = 1.0, max_samples: int = 0,
                         labels_for_accounting: bool = True,
                         record_trace: bool = False):
    """Deprecated: build a `ServingConfig(path="sharded", ...)` and call
    `repro.serving.serve` instead (pass an explicit Mesh via
    ``serve(..., mesh=...)``). Returns the facade's `ServeReport`
    (dict-compatible with the legacy result)."""
    from repro.serving.api import ServingConfig, _warn_legacy, serve
    _warn_legacy("serve_stream_sharded")
    config = ServingConfig(path="sharded", batch_size=batch_size,
                           replicas=replicas, overlap=overlap,
                           overlap_depth=overlap_depth,
                           side_info=side_info, beta=beta,
                           max_samples=max_samples,
                           labels_for_accounting=labels_for_accounting,
                           record_trace=record_trace)
    return serve(runtime, params, stream, cost, config, mesh=mesh)
