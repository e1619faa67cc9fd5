"""Batched edge/cloud serving runtime — the vectorized production path.

`serve_stream` (simulator.py) dispatches one sample per device call: a
host-side bandit round, an `edge_fn` launch with batch size 1, and an
immediate `cloud_fn` launch on offload. Throughput is bounded by Python
dispatch, not hardware — the gap Dynamic Split Computing identifies
between simulated and deployable split inference.

This module serves the same stream in micro-batches of B samples:

  1. **ingest** — `data.stream.microbatches` groups the sample stream;
  2. **select** — `SplitEEController.choose_splits` draws all B arms
     from the bandit state frozen at the batch boundary (delayed
     feedback: the batch's own updates have not landed yet);
  3. **edge** — samples are bucketed by chosen depth and each bucket is
     one `edge_fn`/`edge_fn_s` launch. Buckets are padded to power-of-two
     row counts so at most log2(B)+1 shapes are ever compiled per
     function (depth itself is a traced argument — no recompile across
     depths). With ``edge_mode="scan"`` this step is replaced by
     `serving.scan_edge._edge_phase_scan`: one masked scan-over-layers
     launch for the whole micro-batch, bit-identical outputs;
  4. **cloud** — non-exiting samples land in an `OffloadQueue`; at the
     batch boundary the queue flushes one batched `cloud_fn` launch per
     depth bucket (again pow2-padded);
  5. **update** — `SplitEEController.update_batch` applies the whole
     batch's rewards as one vectorized reduce.

Semantics: with B = 1 the pipeline is *bit-identical* to `serve_stream`
(same arms, exits, rewards, costs, offload bytes — the differential test
pins this). With B > 1 the policy is UCB with feedback delayed by up to
B-1 rounds, the standard batched-bandit relaxation; the regret penalty
is additive in B, not multiplicative (Joulani et al., 2013).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.controller import SplitEEController
from repro.core.rewards import CostModel
from repro.data.stream import microbatches
from repro.serving.offload_codec import OffloadCodec
from repro.serving.simulator import EdgeCloudRuntime
from repro.serving.tracing import Tracer


def _pow2(k: int) -> int:
    """Smallest power of two >= k (bucket capacity; bounds compilations)."""
    return 1 << (k - 1).bit_length() if k > 1 else 1


def _bucket_cap(k: int, multiple: int = 1) -> int:
    """Bucket row capacity: pow2-padded, rounded up to `multiple`.

    `multiple` is the sharded runtime's replica count — the cap must
    divide over the mesh's data axis or `sanitize_spec` would silently
    fall back to replication. Rounding the pow2 cap up keeps the
    compiled-shape count bounded (<= log2(B)+1 distinct caps per
    function); with `multiple` = 1 this is exactly `_pow2`.
    """
    cap = max(_pow2(k), multiple)
    return -(-cap // multiple) * multiple


def _offload_scale(codec: Optional[OffloadCodec],
                   runtime: EdgeCloudRuntime, seq_len: int) -> float:
    """Scale on the bandit's communication term: wire bytes over
    full-dtype activation bytes (1.0 without a codec). Deterministic per
    (codec, shape) so every replica/host prices offloads identically."""
    if codec is None:
        return 1.0
    cfg = runtime.cfg
    return codec.cost_ratio(seq_len, cfg.d_model,
                            jnp.dtype(cfg.dtype).itemsize)


def _pad_rows(arr: np.ndarray, cap: int) -> np.ndarray:
    """Pad the leading axis to `cap` rows by repeating the last row."""
    k = arr.shape[0]
    if k == cap:
        return arr
    reps = np.repeat(arr[-1:], cap - k, axis=0)
    return np.concatenate([arr, reps], axis=0)


class PendingFlush:
    """In-flight cloud launches from ``OffloadQueue.flush_async``.

    Holds the un-materialized device arrays returned by the dispatched
    `cloud_fn` calls (JAX async dispatch: the launches are enqueued on
    the device, the Python call has already returned). ``resolve()``
    blocks on the device->host transfer and returns the
    ``{slot: (conf_L, pred_L)}`` map — deferring that call is what lets
    the sharded and distributed runtimes keep up to ``depth`` batches of
    cloud compute in flight behind later batches' edge selection and
    launches (the pipeline ring in ``flush_async``).
    """

    def __init__(self, launches, slot_bytes: Optional[Dict[int, int]] = None):
        # [(slots, conf_dev, pred_dev)] in depth order — the dispatch
        # order is fixed at flush time, so resolution order (and thus
        # slot bookkeeping) is deterministic regardless of when
        # ``resolve`` is called.
        self._launches = launches
        self._result: Optional[Dict[int, tuple]] = None
        # wire bytes actually shipped per offloaded slot, recorded at
        # dispatch time (the flush measured its own payload) — the byte
        # accounting reads this instead of re-deriving from config dtype
        self.slot_bytes: Dict[int, int] = slot_bytes or {}

    def __len__(self):
        if self._result is not None:
            return len(self._result)
        return sum(len(slots) for slots, _, _ in self._launches)

    @property
    def resolved(self) -> bool:
        return self._result is not None

    def resolve(self) -> Dict[int, tuple]:
        if self._result is None:
            out: Dict[int, tuple] = {}
            for slots, conf_dev, pred_dev in self._launches:
                conf_np = np.asarray(conf_dev)
                pred_np = np.asarray(pred_dev)
                for j, slot in enumerate(slots):
                    out[slot] = (float(conf_np[j]), int(pred_np[j]))
            self._result = out
            self._launches = []
        return self._result


class OffloadQueue:
    """Accumulates offloaded activations; flushes batched cloud calls.

    Rows live host-side as numpy (one device->host transfer per edge
    bucket, no per-row device slicing — per-index slices would compile a
    fresh XLA gather each). `flush()` issues one `cloud_fn` launch per
    distinct depth with all queued rows stacked (padded to a pow2 row
    count, so compilations are bounded by log2(B)+1 shapes) and returns
    ``{slot: (conf_L, pred_L)}`` for the batch's bookkeeping.

    ``flush_async()`` is the overlap-mode variant: it dispatches the same
    launches but returns a `PendingFlush` whose ``resolve()`` the caller
    defers — the queue clears at dispatch time, so the next batch's rows
    accumulate into a fresh queue while the flushed launches are still in
    flight. With ``depth=K`` the queue keeps a ring of in-flight
    `PendingFlush` slots and force-resolves the oldest once more than K
    are outstanding, so at most K flushes are ever in flight no matter
    how long the caller defers. ``flush()`` is exactly
    ``flush_async().resolve()``.
    """

    def __init__(self, runtime: EdgeCloudRuntime, params, *, put=None,
                 codec: Optional[OffloadCodec] = None):
        self.runtime = runtime
        self.params = params
        # host->device placement hook: the sharded runtime passes a
        # device_put that spreads the padded rows over the mesh's data
        # axis; default is plain single-device placement.
        self.put = put if put is not None else jnp.asarray
        # optional quantized-offload codec: the flush encodes the queued
        # rows to the wire format and hands the cloud the lossy decode —
        # the single edge->cloud handoff shared by all runtimes
        self.codec = codec
        self.rows: Dict[int, List[np.ndarray]] = {}   # depth -> [(S, D)]
        self.slots: Dict[int, List[int]] = {}
        self.inflight: List[PendingFlush] = []        # flush_async ring

    def add_rows(self, depth: int, hidden_rows: np.ndarray,
                 slots: List[int]):
        """hidden_rows: (k, S, D) host array, one row per queued sample."""
        self.rows.setdefault(depth, []).extend(hidden_rows)
        self.slots.setdefault(depth, []).extend(slots)

    def __len__(self):
        return sum(len(v) for v in self.slots.values())

    def flush_async(self, *, min_rows: int = 1,
                    depth: Optional[int] = None) -> PendingFlush:
        """Dispatch one `cloud_fn` launch per queued depth; don't block.

        ``min_rows`` sets the pad floor AND rounding multiple (the
        sharded runtime passes the replica count so every launch divides
        over the data axis).

        ``depth`` bounds the flush pipeline: the returned `PendingFlush`
        joins a ring of in-flight slots, and once more than ``depth``
        are unresolved the oldest is resolved (blocking) in dispatch
        order — FIFO, so the forced resolution is exactly the one the
        caller would have performed next (``resolve`` is idempotent).
        ``None`` leaves the ring unbounded (the caller owns resolution).
        """
        if depth is not None and depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        launches = []
        slot_bytes: Dict[int, int] = {}
        for d in sorted(self.rows):
            slots = self.slots[d]
            hidden = _pad_rows(np.stack(self.rows[d]),
                               _bucket_cap(len(slots), min_rows))
            if self.codec is not None:
                enc = self.codec.encode(hidden)
                hidden = self.codec.decode(enc)
                rb = enc.row_bytes
            else:
                rb = int(hidden[0].nbytes)
            conf_L, pred_L = self.runtime.cloud_fn(
                self.params, self.put(hidden), jnp.int32(d))
            launches.append((list(slots), conf_L, pred_L))
            for s in slots:
                slot_bytes[s] = rb
        self.rows.clear()
        self.slots.clear()
        pending = PendingFlush(launches, slot_bytes)
        if depth is not None:
            self.inflight = [p for p in self.inflight if not p.resolved]
            self.inflight.append(pending)
            while len(self.inflight) > depth:
                self.inflight.pop(0).resolve()
        return pending

    def flush(self) -> Dict[int, tuple]:
        return self.flush_async().resolve()


def _edge_phase(runtime: EdgeCloudRuntime, params, tokens: np.ndarray,
                arms: np.ndarray, cost: CostModel, queue: OffloadQueue, *,
                side_info: bool, put=jnp.asarray, replicas: int = 1):
    """Run one micro-batch's edge pass: one launch per distinct depth.

    Shared by the batched and sharded runtimes — they differ only in
    host->device placement (``put``) and the bucket-cap rounding multiple
    (``replicas``). Samples that don't exit are queued on ``queue``;
    returns (conf_paths, batch_preds) indexed by batch slot.
    """
    B = len(arms)
    conf_paths: List[Optional[np.ndarray]] = [None] * B
    batch_preds = [0] * B
    for arm in np.unique(arms):
        arm = int(arm)
        idx = np.nonzero(arms == arm)[0]
        toks = _pad_rows(tokens[idx], _bucket_cap(len(idx), replicas))
        jb = {"tokens": put(toks)}
        if side_info:
            conf_all, pred_all, hidden = runtime.edge_fn_s(
                params, jb, jnp.int32(arm))
            conf_np = np.asarray(conf_all)                 # (L, cap)
            pred_np = np.asarray(pred_all)
            for j, s in enumerate(idx):
                conf_paths[s] = conf_np[: arm + 1, j]
                batch_preds[s] = int(pred_np[arm, j])
        else:
            conf_v, pred_v, hidden = runtime.edge_fn(
                params, jb, jnp.int32(arm))
            conf_np = np.asarray(conf_v)                   # (cap,)
            pred_np = np.asarray(pred_v)
            for j, s in enumerate(idx):
                conf_paths[s] = conf_np[j:j + 1]
                batch_preds[s] = int(pred_np[j])
        keep_j = [j for j, s in enumerate(idx)
                  if not (float(conf_paths[s][-1]) >= cost.alpha
                          or arm + 1 == cost.num_layers)]
        if keep_j:
            h_np = np.asarray(hidden)            # one transfer per bucket
            queue.add_rows(arm, h_np[keep_j],
                           [int(idx[j]) for j in keep_j])
    return conf_paths, batch_preds


class _BatchedSession:
    """Incremental driver of the batched micro-batch schedule.

    One `push(batch)` runs exactly the per-batch body of the offline
    loop (select → edge → cloud flush → delayed-feedback fold), so the
    one-shot `_serve_stream_batched` and the push-mode `api.Engine` are
    the same machinery by construction. `result()` is non-destructive —
    a session can report mid-stream and keep serving.
    """

    def __init__(self, runtime: EdgeCloudRuntime, params, cost: CostModel,
                 *, batch_size: int = 32, side_info: bool = False,
                 beta: float = 1.0, labels_for_accounting: bool = True,
                 record_trace: bool = False, edge_mode: str = "bucketed",
                 controller_kwargs: Optional[Dict[str, Any]] = None,
                 codec: Optional[OffloadCodec] = None):
        # lazy import: scan_edge imports OffloadQueue/_pad_rows from here
        from repro.serving.scan_edge import select_edge_phase
        self.runtime = runtime
        self.params = params
        self.cost = cost
        self.batch_size = batch_size
        self.side_info = side_info
        self.edge_mode = edge_mode
        self._edge_phase = select_edge_phase(edge_mode)
        self.labels_for_accounting = labels_for_accounting
        self.ctl = SplitEEController(cost, beta=beta, side_info=side_info,
                                     **(controller_kwargs or {}))
        self.codec = codec
        self.queue = OffloadQueue(runtime, params, codec=codec)
        self.correct: List[int] = []
        self.preds: List[int] = []
        self.trace: Optional[Dict[str, list]] = (
            {"conf_path": [], "conf_L": []} if record_trace else None)
        self.n = 0
        self.batch_sizes: List[int] = []   # fill levels of pushed batches
        self.tracer = Tracer()

    def push(self, batch):
        """Serve one micro-batch (any size >= 1; ragged tails included).
        An empty push is a no-op — a scheduler tick or drain that formed
        nothing must not spend a bandit round."""
        if not batch:
            return
        B = len(batch)
        tr = self.tracer
        with tr.span("splitee.batched.push"):
            tr.count("splitee.batched.batches")
            tr.count("splitee.batched.rows", B)
            self.batch_sizes.append(B)
            with tr.span("splitee.batched.select"):
                arms = self.ctl.choose_splits(B)
                tokens = np.stack([np.asarray(s["tokens"]) for s in batch])
            seq_len = tokens.shape[1]

            # ---- edge: per-depth bucket launches, or one masked scan ---
            with tr.span("splitee.batched.edge"):
                conf_paths, batch_preds = self._edge_phase(
                    self.runtime, self.params, tokens, arms, self.cost,
                    self.queue, side_info=self.side_info)

            # ---- cloud: flush the offload queue in depth buckets -------
            with tr.span("splitee.batched.cloud"):
                # one cloud_fn launch per queued depth
                tr.count("splitee.batched.cloud_launches",
                         len(self.queue.rows))
                pending = self.queue.flush_async()
                cloud = pending.resolve()

            # ---- delayed-feedback batch update -------------------------
            with tr.span("splitee.batched.fold"):
                conf_Ls: List[Optional[float]] = [None] * B
                obs = [0] * B
                for s, (c_L, p_L) in cloud.items():
                    conf_Ls[s] = c_L
                    batch_preds[s] = p_L
                    # bytes the flush actually shipped for this slot
                    # (codec wire format when one is set, raw activation
                    # bytes otherwise)
                    obs[s] = pending.slot_bytes[s]
                self.ctl.update_batch(
                    arms, conf_paths, conf_Ls, obs,
                    offload_scale=_offload_scale(self.codec, self.runtime,
                                                 seq_len))

                self.preds.extend(batch_preds)
                if self.trace is not None:
                    self.trace["conf_path"].extend(conf_paths)
                    self.trace["conf_L"].extend(conf_Ls)
                if self.labels_for_accounting:
                    for s, sample in enumerate(batch):
                        if "labels" in sample:
                            self.correct.append(
                                int(batch_preds[s] == int(sample["labels"])))
                self.n += B

    def drain(self):
        """Synchronous path: every flush resolved at its own boundary —
        nothing in flight. Kept for interface parity with the sharded
        session, whose drain resolves the overlap ring."""

    def result(self) -> Dict[str, Any]:
        ctl = self.ctl
        hist = {k: np.asarray(v) for k, v in ctl.history.items()}
        tot = ctl.totals
        out = {
            "n": self.n,
            "batch_size": self.batch_size,
            "preds": np.asarray(self.preds),
            # scalar accounting comes from the controller's O(1)
            # aggregates so it survives record_history=False
            "cost_total": float(tot["cost"]),
            "offload_frac": (1.0 - tot["exited"] / tot["served"]
                             if tot["served"] else 0.0),
            "offload_bytes": int(tot["offload_bytes"]),
            "arms": hist["arm"],
            "rewards": hist["reward"],
            "exited": hist["exited"],
            "state": ctl.snapshot(),
            "telemetry": self.tracer.snapshot(),
        }
        if self.correct:
            out["accuracy"] = float(np.mean(self.correct))
        if self.trace is not None:
            out["trace"] = self.trace
        return out


def _serve_stream_batched(runtime: EdgeCloudRuntime, params, stream,
                          cost: CostModel, *, batch_size: int = 32,
                          side_info: bool = False, beta: float = 1.0,
                          max_samples: int = 0,
                          labels_for_accounting: bool = True,
                          record_trace: bool = False,
                          edge_mode: str = "bucketed",
                          controller_kwargs: Optional[Dict[str, Any]] = None,
                          codec: Optional[OffloadCodec] = None,
                          ) -> Dict[str, Any]:
    """Offline driver: replay a finite stream through a batched session."""
    sess = _BatchedSession(runtime, params, cost, batch_size=batch_size,
                           side_info=side_info, beta=beta,
                           labels_for_accounting=labels_for_accounting,
                           record_trace=record_trace, edge_mode=edge_mode,
                           controller_kwargs=controller_kwargs, codec=codec)
    for batch in microbatches(stream, batch_size, max_samples):
        sess.push(batch)
    return sess.result()


def serve_stream_batched(runtime: EdgeCloudRuntime, params, stream,
                         cost: CostModel, *, batch_size: int = 32,
                         side_info: bool = False, beta: float = 1.0,
                         max_samples: int = 0,
                         labels_for_accounting: bool = True,
                         record_trace: bool = False):
    """Deprecated: build a `ServingConfig(path="batched", ...)` and call
    `repro.serving.serve` instead. Returns the facade's `ServeReport`
    (dict-compatible with the legacy result)."""
    from repro.serving.api import ServingConfig, _warn_legacy, serve
    _warn_legacy("serve_stream_batched")
    config = ServingConfig(path="batched", batch_size=batch_size,
                           side_info=side_info, beta=beta,
                           max_samples=max_samples,
                           labels_for_accounting=labels_for_accounting,
                           record_trace=record_trace)
    return serve(runtime, params, stream, cost, config)
