"""Out-of-core GNN trainer — the paper's end-to-end system (§3, Fig. 3/4).

Wires together every Helios component:
  topology  -> host tier (CSRGraph)
  features  -> 4-tier HeteroCache over the FeatureStore ("SSDs")
  IO        -> AsyncIOEngine (per-shard queues, decoupled completion)
  schedule  -> PipelineExecutor with the deep GNN-aware operator plan
  compute   -> jit'd GraphSAGE/GCN/GAT step

``train`` reports the host's wall clock: the run's ``wall_s`` and each
operator's stage timing, plus the cache and IO counters.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hotness as hotness_mod
from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import AsyncIOEngine, FeatureStore
from repro.core.pipeline import Operator, PipelineExecutor
from repro.core.policy import make_policy
from repro.gnn.graph import CSRGraph
from repro.gnn.models import (edge_counts, init_gnn_params,
                              make_gnn_train_step)
from repro.gnn.sampling import NeighborSampler, draw_unique
from repro.obs import trace as _trace
from repro.train.optim import adamw


# a batch's feature array holds a rung of this ladder of rows, not the
# sampler's worst case: rows past the batch's real ones are zeros that no
# edge and no loss reads, so the rungs bound the step's compiles while the
# upload stays near the real rows
ROW_HEADROOM = 1.05        # over the presample epoch's largest batch


def row_bucket(n: int) -> int:
    """Smallest rung of 2^k x {1, 1.25, 1.5, 1.75} that holds ``n`` rows."""
    base = 1 << max((max(n, 1) - 1).bit_length() - 1, 2)
    return next(base * q // 4 for q in (4, 5, 6, 7, 8) if base * q // 4 >= n)


@dataclass
class TrainerConfig:
    model: str = "sage"            # sage | gcn | gat
    hidden: int = 256
    batch_size: int = 1024
    fanouts: tuple = (25, 10)
    mode: str = "helios"           # the only system; kept for configs
    device_cache_frac: float = 0.05
    host_cache_frac: float = 0.10
    prefetch_depth: int = 2
    io_worker_budget: float = 0.3
    presample_batches: int = 8
    cache_policy: str = "static"   # static | online (core.policy)
    fused_lookup: bool = True      # fused plan+dedup+tier-split cache lookup
                                   # with deduplicated miss lists (PR 7);
                                   # False = PR-3 host plan() ablation
    refresh_every: int = 8         # batches between refresh checks (online)
    prefetch_rows: int = 0         # predicted-hot rows pulled per batch by
                                   # the prefetch operator (0 = disabled)
    policy_half_life: float = 16.0
    policy_hysteresis: float = 0.1
    lr: float = 1e-3
    # trainable embeddings (the write-path workload): gradient-updated
    # feature rows ride the cache's write-back tiers; requires a store
    # opened with writable=True
    train_embeddings: bool = False
    embedding_lr: float = 0.05
    embedding_momentum: float = 0.0  # SGD momentum over the embedding rows;
                                   # >0 keeps per-row velocity in a SECOND
                                   # mutable table (its own store + cache)
                                   # riding the same write-back/flush path
    embedding_adam: float = 0.0    # Adam beta2: >0 keeps the per-row second
                                   # moment in a THIRD mutable table on the
                                   # same write-back/flush path; combines
                                   # with embedding_momentum as beta1-style
                                   # velocity (lazy sparse Adam)
    embedding_adam_eps: float = 1e-8
    embedding_flush_every: int = 0  # batches between flush barriers
                                   # (0 = flush only at epoch end / demote)
    write_policy: str = "writeback"  # writeback | writethrough (ablation)
    write_combine_rows: int = 0    # coalesce flush-on-demote batches smaller
                                   # than this into one combined ticket
                                   # (0 = one ticket per demotion batch)
    # fault injection + recovery (ft.chaos): "env" reads HELIOS_CHAOS,
    # None disables, or pass a ChaosSchedule; the retry knobs build one
    # RetryPolicy shared by the feature/optimizer-table engines
    chaos: object | None = "env"
    io_deadline_s: float | None = None  # per-attempt virtual deadline
    io_max_retries: int = 4
    io_backoff_s: float = 1e-3     # exponential backoff base (virtual s)
    # per-stream-class shard scheduling + back-pressure (docs/streams.md):
    # "wfq" = strict demand priority over a weighted-fair bulk tail,
    # "fifo" = the pre-congestion-control arrival order (ablation);
    # io_qwait_high_s engages prefetch/checkpoint throttling when demand
    # p99 queue delay (virtual s) crosses it, io_qwait_low_s releases
    # (None = high/2; both None = back-pressure off)
    io_sched: str = "wfq"
    io_class_weights: dict | None = None
    io_qwait_high_s: float | None = None
    io_qwait_low_s: float | None = None
    seed: int = 0

    def __post_init__(self):
        if self.mode != "helios":
            raise ValueError(f"TrainerConfig.mode must be 'helios', got "
                             f"{self.mode!r}")

    def retry_policy(self):
        from repro.ft.chaos import DEFAULT_RETRY, RetryPolicy
        if (self.io_deadline_s is None and self.io_max_retries == 4
                and self.io_backoff_s == 1e-3):
            return DEFAULT_RETRY
        return RetryPolicy(max_retries=self.io_max_retries,
                           backoff_base_s=self.io_backoff_s,
                           deadline_s=self.io_deadline_s)


class TrainableEmbeddingTable:
    """Trainable node embeddings living in the FeatureStore.

    The feature rows ARE the learnable parameters (MariusGNN-style
    out-of-core embedding training): each step applies the SGD delta
    ``-lr * dL/dfeats`` through ``HeteroCache.apply_delta`` — a
    read-modify-write against the LIVE row value, so concurrent pipeline
    batches that touch the same hot rows compose their updates instead of
    overwriting each other with stale absolute values.  Hot rows mutate in
    their cache tier and ride flush-on-demote; cold rows write through.
    The epoch-boundary ``flush()`` barrier makes storage authoritative for
    checkpointing."""

    def __init__(self, cache: HeteroCache, lr: float,
                 momentum_cache: HeteroCache | None = None,
                 momentum: float = 0.0,
                 adam_cache: HeteroCache | None = None,
                 adam_beta2: float = 0.0, adam_eps: float = 1e-8):
        self.cache = cache
        self.lr = lr
        # optimizer state as SIBLING mutable tables: per-row velocity (and,
        # for Adam, the per-row second moment) lives in its own store
        # behind its own write-back cache, so optimizer rows ride
        # flush-on-demote / epoch barriers exactly like the embedding rows
        # they accelerate
        self.mom = momentum_cache
        self.mu = momentum
        self.v2 = adam_cache
        self.b2 = adam_beta2
        self.eps = adam_eps
        self._t = 0                     # global step for bias correction
        self._mu_lock = threading.Lock()

    def apply_grads(self, ids: np.ndarray, grads: np.ndarray,
                    wait: bool = True):
        """``wait=False`` leaves the storage write-through ticket in
        flight (split-phase) — the caller completes it a batch later via
        ``cache.complete_write``, hiding the write under device compute."""
        grads = np.asarray(grads)
        if self.mom is None and self.v2 is None:
            return self.cache.apply_delta(ids, -self.lr * grads, wait=wait)
        # optimizer-state RMW (duplicate ids contribute their summed
        # gradient, matching apply_delta's own dup rule).  The lock makes
        # the read-update-write atomic against concurrent pipeline batches
        # sharing hot rows.
        ids = np.asarray(ids)
        uniq, inv = np.unique(ids, return_inverse=True)
        summed = np.zeros((len(uniq), grads.shape[1]), grads.dtype)
        np.add.at(summed, inv, grads)
        with self._mu_lock:
            if self.mom is not None:
                # velocity: v <- mu*v + g
                v = self.mu * self.mom.gather(uniq) + summed
                self.mom.write_planned(uniq, v)
            else:
                v = summed
            if self.v2 is None:
                delta = -self.lr * v
            else:
                # lazy sparse Adam: the second moment updates only for rows
                # present in the batch, and bias correction uses the GLOBAL
                # step (per-row step counts are not tracked — the standard
                # out-of-core embedding compromise)
                self._t += 1
                m2 = (self.b2 * self.v2.gather(uniq)
                      + (1.0 - self.b2) * summed ** 2)
                self.v2.write_planned(uniq, m2)
                denom = np.sqrt(m2 / (1.0 - self.b2 ** self._t)) + self.eps
                delta = -self.lr * v / denom
        return self.cache.apply_delta(uniq, delta, wait=wait)


class OutOfCoreGNNTrainer:
    def __init__(self, graph: CSRGraph, store: FeatureStore,
                 cfg: TrainerConfig | None = None):
        cfg = cfg if cfg is not None else TrainerConfig()
        self.g, self.store, self.cfg = graph, store, cfg
        if cfg.train_embeddings and not store.writable:
            raise ValueError("train_embeddings needs a FeatureStore opened "
                             "with writable=True (the embedding rows are "
                             "the parameters)")
        self.sampler = NeighborSampler(graph, cfg.fanouts, cfg.seed)

        self.io = self._engine(store)

        # --- hotness pre-sampling + cache placement (paper §3.2.2) -------
        # presample on a SEPARATE sampler so the training sampler's rng
        # stream doesn't depend on the presample configuration
        presampled = []
        hot = hotness_mod.presample_gnn(
            NeighborSampler(graph, cfg.fanouts, cfg.seed + 1),
            cfg.batch_size, cfg.presample_batches,
            graph.n_vertices, cfg.seed, batch_rows=presampled)
        # the feature rows each batch uploads: seeded from the presample
        # epoch so the step compiles before training, raised (never
        # lowered) when a batch's real rows pass it
        self._rows = row_bucket(int(max(presampled, default=0)
                                    * ROW_HEADROOM))
        self._rows_lock = threading.Lock()
        dev_rows = int(graph.n_vertices * cfg.device_cache_frac)
        host_rows = int(graph.n_vertices * cfg.host_cache_frac)
        policy = make_policy(cfg.cache_policy, graph.n_vertices,
                             presample=hot, refresh_every=cfg.refresh_every,
                             half_life=cfg.policy_half_life,
                             hysteresis=cfg.policy_hysteresis)
        self.cache = HeteroCache(store, None, dev_rows, host_rows, self.io,
                                 policy=policy,
                                 write_policy=cfg.write_policy,
                                 write_combine_rows=cfg.write_combine_rows,
                                 fused=cfg.fused_lookup)

        # --- model + optimizer -------------------------------------------
        key = jax.random.key(cfg.seed)
        self.params = init_gnn_params(key, cfg.model, store.row_dim,
                                      cfg.hidden, graph.n_classes)
        self.opt = adamw(cfg.lr)
        self.state = {"params": self.params, "opt": self.opt.init(self.params)}
        self.step_fn = make_gnn_train_step(
            cfg.model, self.opt, cfg.batch_size,
            embedding_grads=cfg.train_embeddings)
        # optimizer-state tables: per-row velocity (momentum) and second
        # moment (Adam) in their own writable stores (zero-initialised
        # memmaps) behind host-tier write-back caches — the same
        # mutable-tier machinery, sibling instances
        def _opt_table(suffix):
            st = FeatureStore(store.path + suffix, store.n_rows,
                              store.row_dim, dtype=store.dtype,
                              n_shards=store.n_shards,
                              create=True, writable=True)
            c = HeteroCache(
                st, None, 0, host_rows, self._engine(st),
                write_policy=cfg.write_policy,
                write_combine_rows=cfg.write_combine_rows,
                fused=cfg.fused_lookup)
            c._owns_engine = True
            return st, c

        self.mom_store = self.mom_cache = None
        self.adam_store = self.adam_cache = None
        if cfg.train_embeddings and cfg.embedding_momentum > 0.0:
            self.mom_store, self.mom_cache = _opt_table("_momentum")
        if cfg.train_embeddings and cfg.embedding_adam > 0.0:
            self.adam_store, self.adam_cache = _opt_table("_adam")
        self.embeddings = (TrainableEmbeddingTable(self.cache,
                                                   cfg.embedding_lr,
                                                   self.mom_cache,
                                                   cfg.embedding_momentum,
                                                   self.adam_cache,
                                                   cfg.embedding_adam,
                                                   cfg.embedding_adam_eps)
                           if cfg.train_embeddings else None)
        self.metrics_log = []
        # double-buffered prefetch: the ticket issued for batch i stays in
        # flight until batch i+1's operator completes it
        self._pf_pending = None
        self._pf_lock = threading.Lock()
        self._wb_batches = 0
        # split-phase embedding write-back: batch i's storage ticket stays
        # in flight until batch i+1's operator completes it
        self._wb_pending = None

    def _engine(self, store: FeatureStore) -> AsyncIOEngine:
        cfg = self.cfg
        return AsyncIOEngine(store, worker_budget=cfg.io_worker_budget,
                             chaos=cfg.chaos, retry=cfg.retry_policy(),
                             sched=cfg.io_sched,
                             class_weights=cfg.io_class_weights,
                             qwait_high_s=cfg.io_qwait_high_s,
                             qwait_low_s=cfg.io_qwait_low_s)

    # -----------------------------------------------------------------
    def _operators(self):
        cfg = self.cfg

        def op_sample(ctx):
            ctx["mb"] = self.sampler.sample(ctx["seeds"])

        # the tier plan, the gathers, and the stats accounting all live in
        # HeteroCache's split-phase API — the operators only phase it
        def op_io_submit(ctx):
            mb = ctx["mb"]
            cap = len(mb.nodes)
            with self._rows_lock:
                want = min(row_bucket(mb.n_real), cap)
                ctx["bucket_rose"] = want > self._rows
                self._rows = max(self._rows, want)
                n_rows = min(self._rows, cap)
            ctx["pending"] = self.cache.submit_planned(mb.all_nodes,
                                                       n_rows=n_rows)

        def op_cache_lookup(ctx):
            self.cache.lookup_planned(ctx["pending"])

        def op_io_complete(ctx):
            ctx["out"] = self.cache.complete_planned(ctx["pending"])

        def op_cache_refresh(ctx):
            # asynchronous tier migration on the io resource: placement
            # updates hide under the device's batch_build/train work
            self.cache.maybe_refresh()

        def op_prefetch(ctx):
            # policy-driven prefetch on the io resource, double-buffered:
            # this batch ISSUES its admission ticket without waiting and
            # COMPLETES the ticket the previous batch left in flight, so
            # the admission read hides under a whole batch of other work
            # instead of blocking inside the operator
            with self._pf_lock:
                prev, self._pf_pending = (
                    self._pf_pending,
                    self.cache.maybe_prefetch(cfg.prefetch_rows, wait=False))
            if prev is not None:
                self.cache.complete_prefetch(prev)

        def op_batch_build(ctx):
            # the feature array holds the batch's row bucket (its real rows,
            # then zeros); the index, mask and label tensors keep the
            # sampler's static maxima, and every index is below n_real
            mb = ctx["mb"]
            feats = ctx["feats"] = jnp.asarray(ctx["out"])
            ctx["tensors"] = (
                tuple(jnp.asarray(b.src_pos) for b in mb.blocks),
                tuple(jnp.asarray(b.dst_pos) for b in mb.blocks),
                tuple(jnp.asarray(b.edge_mask) for b in mb.blocks),
                jnp.asarray(mb.labels),
            )
            # the batch's counters, summed per stage by the executor
            return {"feature_rows": feats.shape[0], "real_rows": mb.n_real,
                    "h2d_bytes": feats.nbytes + sum(
                        a.nbytes for a in jax.tree.leaves(ctx["tensors"])),
                    "bucket_rises": int(ctx["bucket_rose"]),
                    **edge_counts(cfg.model, mb.blocks, feats.shape[0],
                                  mb.n_real)}

        def op_train(ctx):
            src, dst, em, labels = ctx["tensors"]
            upload = None
            tr = _trace.TRACER
            if tr is not None and tr.enabled:
                # traced runs only: wait out the feature upload before the
                # dispatch, so the transfer is timed apart from the step
                # (this serialises the dispatch, about 1 ms, behind it)
                feats = ctx["feats"]
                with tr.span("pipe.train.upload", track="device", cat="h2d",
                             args={"batch": ctx["batch"],
                                   "bytes": feats.nbytes}) as sp:
                    feats.block_until_ready()
                upload = {"upload_s": sp.wall_s}
            if cfg.train_embeddings:
                self.state, m, fgrad = self.step_fn(self.state, ctx["feats"],
                                                    src, dst, em, labels)
                ctx["feat_grad"] = np.asarray(fgrad)
            else:
                self.state, m = self.step_fn(self.state, ctx["feats"], src,
                                             dst, em, labels)
            ctx["metrics"] = jax.tree.map(float, m)
            self.metrics_log.append(ctx["metrics"])
            return upload

        def op_embedding_writeback(ctx):
            # gradient-updated embedding rows ride the cache write path on
            # the io resource, SPLIT-PHASE: resident rows mutate in their
            # tier at submit (dirty; flush-on-demote / epoch flush covers
            # storage), cold rows' write-through ticket stays IN FLIGHT
            # across pipeline batches — this batch submits its own ticket
            # and completes the one the previous batch left pending, so
            # the storage write hides under a whole batch of other work
            mb = ctx["mb"]
            n = mb.n_real
            # the RMW read inside apply_grads blocks on a storage ticket —
            # keep it OUTSIDE _pf_lock so the prefetch operator (which
            # contends on the same lock for its double-buffer swap) never
            # serializes behind it
            pw = self.embeddings.apply_grads(mb.nodes[:n],
                                             ctx["feat_grad"][:n],
                                             wait=False)
            with self._pf_lock:
                prev, self._wb_pending = self._wb_pending, pw
            if prev is not None:
                self.cache.complete_write(prev)
            if cfg.embedding_flush_every > 0:
                with self._pf_lock:
                    self._wb_batches += 1
                    due = self._wb_batches % cfg.embedding_flush_every == 0
                if due:
                    # land the just-submitted ticket before the barrier
                    with self._pf_lock:
                        cur, self._wb_pending = self._wb_pending, None
                    if cur is not None:
                        self.cache.complete_write(cur)
                    self.cache.flush()
                    if self.mom_cache is not None:
                        # the optimizer-state tables honor the same
                        # barrier: velocity rows are restart state too
                        self.mom_cache.flush()
                    if self.adam_cache is not None:
                        self.adam_cache.flush()

        plan = [
            Operator("sample", op_sample, "host"),
            Operator("io_submit", op_io_submit, "io", ("sample",)),
            Operator("cache_lookup", op_cache_lookup, "host", ("io_submit",)),
            Operator("io_complete", op_io_complete, "io", ("io_submit",)),
            Operator("cache_refresh", op_cache_refresh, "io",
                     ("io_complete",)),
            Operator("batch_build", op_batch_build, "device",
                     ("cache_lookup", "io_complete")),
            Operator("train", op_train, "device", ("batch_build",)),
        ]
        if cfg.prefetch_rows > 0:
            plan.insert(5, Operator("prefetch", op_prefetch, "io",
                                    ("io_complete",)))
        if cfg.train_embeddings:
            plan.append(Operator("embedding_writeback",
                                 op_embedding_writeback, "io", ("train",)))
        return plan

    # -----------------------------------------------------------------
    def train(self, n_batches: int) -> dict:
        cfg = self.cfg
        pipe = PipelineExecutor(self._operators(),
                                prefetch_depth=cfg.prefetch_depth)

        def make_ctx(i):
            # bounded-cost unique draw: O(batch) expected, not O(n_vertices).
            # The rng is derived from the BATCH INDEX, not a shared stream:
            # the pipeline calls make_ctx from concurrent pipe-batch
            # threads, and a shared Generator is neither thread-safe nor
            # deterministic under interleaving — per-index derivation makes
            # the seed stream reproducible at every prefetch depth
            rng = np.random.default_rng([cfg.seed, 0x5EED, i])
            seeds = draw_unique(rng, self.g.n_vertices, cfg.batch_size)
            return {"seeds": seeds, "batch": i}

        out = pipe.run(make_ctx, n_batches)
        pipe.close()
        # land the last double-buffered prefetch ticket left in flight
        with self._pf_lock:
            pf, self._pf_pending = self._pf_pending, None
            wb, self._wb_pending = self._wb_pending, None
        if pf is not None:
            self.cache.complete_prefetch(pf)
        # harvest the final split-phase embedding write ticket, then the
        # epoch barrier: every dirty embedding row becomes durable on
        # storage through ONE batched (striped, coalesced) write ticket
        if wb is not None:
            self.cache.complete_write(wb)
        epoch_flush = (self.cache.flush() if cfg.train_embeddings else None)
        if self.mom_cache is not None:
            self.mom_cache.flush()
        if self.adam_cache is not None:
            self.adam_cache.flush()
        # atomic snapshots: nothing here can read a concurrent completion
        # or refresh mid-update (the serving path shares these objects)
        cs_snap = self.cache.stats()
        io_snap = self.io.stats.snapshot()
        out["cache"] = {
            "hit_rate": cs_snap.hit_rate,
            "device_hits": cs_snap.device_hits,
            "host_hits": cs_snap.host_hits,
            "storage_misses": cs_snap.storage_misses,
            "policy": self.cache.policy.name,
            "refreshes": cs_snap.refreshes,
            "promotions": cs_snap.promotions,
            "demotions": cs_snap.demotions,
            "virtual_migrate_s": cs_snap.virtual_migrate_s,
            "prefetches": cs_snap.prefetches,
            "prefetched_rows": cs_snap.prefetched_rows,
            "virtual_prefetch_s": cs_snap.virtual_prefetch_s,
        }
        out["io"] = {"requests": io_snap.requests,
                     "bytes": io_snap.bytes,
                     "virtual_s": io_snap.virtual_io_s,
                     "ranges": io_snap.ranges,
                     "span_bytes": io_snap.span_bytes,
                     "write_requests": io_snap.write_requests,
                     "write_bytes": io_snap.write_bytes,
                     "virtual_write_s": io_snap.virtual_write_s,
                     # fault-recovery visibility (chaos legs assert on it)
                     "retries": io_snap.retries,
                     "timeouts": io_snap.timeouts,
                     "transient_errors": io_snap.transient_errors,
                     "virtual_backoff_s": io_snap.virtual_backoff_s,
                     "degraded_events": io_snap.degraded_events,
                     "degraded_skipped_rows":
                         cs_snap.degraded_skipped_rows,
                     # per-stream-class breakdown + back-pressure
                     # visibility (docs/streams.md)
                     "by_class": io_snap.by_class,
                     "throttle_engaged": io_snap.throttle_engaged,
                     "throttle_released": io_snap.throttle_released,
                     "throttled_skipped_rows":
                         cs_snap.throttled_skipped_rows}
        if cfg.train_embeddings:
            cs = cs_snap
            out["writeback"] = {
                "written_rows": cs.written_rows,
                "write_through_rows": cs.write_through_rows,
                "flushed_rows": cs.flushed_rows,
                "flushes": cs.flushes,
                "virtual_write_s": cs.virtual_write_s,
                "virtual_flush_s": cs.virtual_flush_s,
                "epoch_flush_rows": epoch_flush.rows,
                "dirty_after_flush": self.cache.n_dirty,
            }
            if self.mom_cache is not None:
                ms = self.mom_cache.stats
                out["writeback"]["momentum"] = {
                    "written_rows": ms.written_rows,
                    "flushed_rows": ms.flushed_rows,
                    "flushes": ms.flushes,
                    "dirty_after_flush": self.mom_cache.n_dirty,
                }
            if self.adam_cache is not None:
                vs = self.adam_cache.stats
                out["writeback"]["adam"] = {
                    "written_rows": vs.written_rows,
                    "flushed_rows": vs.flushed_rows,
                    "flushes": vs.flushes,
                    "dirty_after_flush": self.adam_cache.n_dirty,
                }
        out["loss_first"] = self.metrics_log[0]["loss"] if self.metrics_log else None
        out["loss_last"] = self.metrics_log[-1]["loss"] if self.metrics_log else None
        return out

    # -----------------------------------------------------------------
    def close(self):
        """Release the IO stack: cache first (closes nothing it doesn't
        own), then the engine this trainer created (joins its workers).
        The optimizer-state caches own their engines and close them
        themselves."""
        self.cache.close()
        self.io.close()
        if self.mom_cache is not None:
            self.mom_cache.close()
        if self.adam_cache is not None:
            self.adam_cache.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
