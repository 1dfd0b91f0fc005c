"""Training cells: minibatch training through
``OutOfCoreGNNTrainer.train``, the trainer's own uniform seed draw.

Set-up builds one trainer, gives it the benchmark's weights, and drives
it through its first steps with the window's own call (``train``); the
reference follows those steps.  The window is one ``train(n)`` call, ``n``
sized from warm steps so that it lasts about ``seconds``; it ends when the
last step's loss is on the host.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

import harness
import refcore
import system
from flops import train_flops_per_seed


class Recorder:
    """Stands in for the trainer's sampler and jitted step while its first
    ``k`` steps run: keeps each step's batch, the rows the step was fed,
    its loss and the states in and out."""

    def __init__(self, trainer, k: int):
        self.tr, self.k = trainer, k
        self.mbs, self.steps = [], []
        self._lock = threading.Lock()
        self._sample = trainer.sampler.sample
        self._step = trainer.step_fn
        trainer.sampler.sample = self.sample
        trainer.step_fn = self.step

    def sample(self, seeds):
        mb = self._sample(seeds)
        with self._lock:
            self.mbs.append(mb)
        return mb

    def step(self, state, feats, src, dst, emask, labels):
        out = self._step(state, feats, src, dst, emask, labels)
        if len(self.steps) < self.k:
            self.steps.append(self._keep(state, out, feats, src, dst, labels))
        return out

    def _keep(self, state, out, feats, src, dst, labels):
        pos = [np.asarray(a) for a in (*src, *dst)]
        with self._lock:
            mbs = list(self.mbs)
        mb = next((m for m in mbs if all(
            np.array_equal(a, b) for a, b in zip(
                pos, [b.src_pos for b in m.blocks]
                + [b.dst_pos for b in m.blocks]))), None)
        n = int(mb.node_mask.sum()) if mb is not None else 0
        return {"mb": mb, "rows": np.asarray(feats[:n]),
                "labels": np.asarray(labels), "loss": float(out[1]["loss"]),
                "state_in": state, "state_out": out[0]}

    def detach(self):
        del self.tr.sampler.sample
        self.tr.step_fn = self._step


def trainer_config(cfg: dict, seed: int):
    from repro.gnn.train import TrainerConfig

    return TrainerConfig(batch_size=cfg["batch_size"], lr=cfg["optimizer"]["lr"],
                         seed=seed, **system.system_kwargs(cfg))


def run(r: harness.Spec) -> dict:
    from repro.gnn.train import OutOfCoreGNNTrainer
    from repro.obs import trace as obs_trace

    import tracing

    cfg, mix = r.cfg, r.traffic
    arch = harness.load_ref(cfg["arch"])
    res = {"timings": {}}
    with harness.CompileCounter() as cc:
        data = system.make_data(cfg)
        res["timings"].update(data.timings)
        t0 = time.perf_counter()
        params0 = system.init_params(arch, cfg, r.seed)
        tr = OutOfCoreGNNTrainer(data.graph, data.store,
                                 trainer_config(cfg, r.seed))
        tr.state = {"params": params0, "opt": tr.opt.init(params0)}
        res["timings"]["trainer_init_s"] = time.perf_counter() - t0
        try:
            t0 = time.perf_counter()
            rec = Recorder(tr, mix["checked_steps"])
            tr.train(mix["checked_steps"])
            rec.detach()
            res["timings"]["checked_steps_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            est = step_time(tr, mix["estimate_steps"])
            res["timings"]["estimate_steps_s"] = time.perf_counter() - t0
            window(r, tr, est, cc, res, tracing, obs_trace)
        finally:
            tr.close()
            del tr
    res["checks"], res["readings"] = check(r, arch, data, params0, rec)
    res["correct"] = harness.within(res["checks"])
    return res


def step_time(tr, steps: int) -> float:
    """Seconds per step once the pipeline is full: ``train(steps)``, timed
    from the second step's loss on the host to the last one's."""
    real, ends = tr.step_fn, []

    def timed(*args):
        out = real(*args)
        float(out[1]["loss"])
        ends.append(time.perf_counter())
        return out
    tr.step_fn = timed
    try:
        tr.train(steps)
    finally:
        tr.step_fn = real
    return (ends[-1] - ends[1]) / (len(ends) - 2)


def window(r, tr, est, cc, res, tracing, obs_trace):
    cfg = r.cfg
    b = cfg["batch_size"]
    n = max(1, int(round(r.seconds / est)))
    c0 = cc.snapshot()[0]
    cache0 = tr.cache.stats()
    io0 = tr.io.stats.snapshot()
    prof = tracer = None
    if r.trace:
        tracer = obs_trace.install()
        prof = tracing.Window(harness.tmp_root())
        prof.__enter__()
    t0 = time.perf_counter()
    out = tr.train(n)
    t1 = time.perf_counter()
    if prof is not None:
        prof.__exit__(None, None, None)
        obs_trace.uninstall()
    res["memory_peak_bytes"] = harness.memory_peak_bytes()
    res["attempted"], res["failed"] = n, 0
    res["end_to_end"] = {"train_seeds_per_s": n * b / (t1 - t0),
                         "setup_s": t0 - r.t_start}
    ctx = {"steps": n, "stages": out["stages"],
           "cache": tr.cache.stats.delta(cache0)._values(),
           "io": {"bytes": tr.io.stats.delta(io0).bytes},
           "compiles": cc.snapshot()[0] - c0,
           "seeds_per_s": n * b / out["wall_s"],
           "flops_per_seed": train_flops_per_seed(
               harness.load_ref(cfg["arch"]).matmuls(cfg))}
    if prof is not None:
        import shutil

        from peaks import peaks

        ctx["peak_flops"] = peaks(r.device["kind"])["bf16_flops_per_s"] \
            if r.device["platform"] == "tpu" else None
        planes = prof.planes()
        ctx["trace"] = tracing.reduce(
            planes, tracing.tracer_spans(tracer, prof, planes))
        shutil.rmtree(prof.log_dir, ignore_errors=True)
    res["ctx"] = ctx


def check(r, arch, data, params0, rec):
    """Compare the first steps with the plain reference; returns the
    checks and ``readings(dot, half=False)``, the compared numbers with the
    reference's products taken by ``dot`` (and, with ``half``, the planted
    half-batch fault)."""
    cfg, lim = r.cfg, r.limits()
    opt = cfg["optimizer"]
    fanouts = tuple(cfg["fanouts"])
    fmax = cfg["batch_size"] * fanouts[0]
    faults = bad_rows = bad_edges = 0
    fn = harness.feature_fn(cfg["feature_dim"])
    key = harness.feature_key(cfg["feature_seed"])

    def rows(ids):
        return fn(key, jax.numpy.asarray(np.asarray(ids, np.int32)))

    trees, prev = [], None
    for st in rec.steps:
        mb = st["mb"]
        if mb is None:
            faults += 1
            continue
        t, f = refcore.tree_from_blocks(
            mb.nodes, mb.node_mask,
            [(x.src_pos, x.dst_pos, x.edge_mask) for x in mb.blocks],
            mb.labels, fanouts)
        faults += f
        t.labels = data.labels[t.seeds]
        faults += int(not np.array_equal(st["labels"], t.labels))
        faults += int(prev is not None and st["state_in"] is not prev)
        prev = st["state_out"]
        bad_edges += refcore.tree_bad_edges(data.rowptr, data.col, t)
        want = harness.feature_rows(cfg["feature_seed"], cfg["feature_dim"],
                                    mb.nodes[mb.node_mask], fn)
        got = st["rows"]
        bad_rows += (len(want) if got.shape != want.shape else
                     int(np.count_nonzero((got != want).any(axis=1))))
        trees.append(t)
    faults += rec.k - len(trees)
    steps = rec.steps[:len(trees)]

    def readings(dot, half=False):
        losses, g1, p_last = refcore.train_reference(
            arch, params0, trees, rows, opt, dot, fmax, half_batch=half)
        gaps = [abs(st["loss"] - b) / abs(b) for st, b in zip(steps, losses)]
        g_prog = jax.tree.map(lambda m: m / (1.0 - opt["b1"]),
                              steps[0]["state_out"]["opt"]["m"])
        g_ref = refcore.leaf_norms(g1)
        grad_gap = refcore.norm_gap(refcore.leaf_norms(g_prog), g_ref)
        keep = refcore.moved_leaves(g_ref)
        d_prog = jax.tree.map(lambda a, b: a - b,
                              steps[-1]["state_out"]["params"], params0)
        d_ref = jax.tree.map(lambda a, b: a - b, p_last, params0)
        upd_gap = refcore.norm_gap(refcore.leaf_norms(d_prog),
                                   refcore.leaf_norms(d_ref), keep)
        return {"loss_gap": gaps[0], "update_gap": upd_gap,
                # diagnostics of the study, not compared: a ReLU unit whose
                # pre-activation lies within rounding of zero switches its
                # derivative, which moves the gradient (and the later
                # steps' losses) far beyond rounding on a few batches
                "grad_gap": grad_gap,
                "loss_gap_steps": max(gaps),
                "grad_diff": refcore.diff_gap(g_prog, g1),
                "update_worst_leaf": refcore.worst_leaf(
                    refcore.leaf_norms(d_prog), refcore.leaf_norms(d_ref))}

    nums = readings(refcore.dot_highest) if trees else {
        k: float("nan") for k in lim}
    checks = {k: harness.check(nums[k], lim[k]) for k in lim}
    checks["bad_rows"] = harness.check(bad_rows, 0)
    checks["bad_edges"] = harness.check(bad_edges, 0)
    checks["faults"] = harness.check(faults, 0)
    return checks, readings
