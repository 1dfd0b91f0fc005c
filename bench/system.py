"""The benchmark's side of the system under test: builds a cell's data
through the program's public types and hands the program its inputs.

The configuration is a dataset: its graph and labels come from its
``graph_seed``, its features from its ``feature_seed``, and both are made
by the first run in a checkout that finds them missing, then reused
(``.bench_data/``).  A run's ``--seed`` draws the weights and the
batches or requests.  This module wraps them in the program's
``CSRGraph`` and ``FeatureStore``.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

import harness


@dataclass
class Data:
    rowptr: np.ndarray
    col: np.ndarray
    labels: np.ndarray
    graph: object            # repro.gnn.graph.CSRGraph
    store: object            # repro.core.iostack.FeatureStore
    timings: dict


def _build_once(path, build) -> None:
    """Make ``path`` by ``build(tmp)`` in a temporary sibling directory,
    then rename it; a directory without ``COMPLETE`` is made again."""
    import shutil

    if (path / "COMPLETE").exists():
        return
    tmp = path.with_name(path.name + ".partial")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    build(tmp)
    (tmp / "COMPLETE").touch()
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)


def feature_table(cfg: dict):
    """The feature table, one per geometry and ``feature_seed`` in a
    checkout (configurations of the same row count and width share it),
    opened read-only."""
    from repro.core.iostack import FeatureStore

    geometry = dict(n_rows=cfg["n_vertices"], row_dim=cfg["feature_dim"],
                    dtype=np.float32, n_shards=cfg["n_shards"])
    path = harness.DATA_DIR / (f"features-{cfg['n_vertices']}x"
                               f"{cfg['feature_dim']}-{cfg['n_shards']}"
                               f"-s{cfg['feature_seed']}")

    def build(tmp):
        store = FeatureStore(str(tmp), create=True, writable=True, **geometry)
        harness.write_features(store, cfg["feature_seed"])
        store.flush()
    _build_once(path, build)
    return FeatureStore(str(path), **geometry)


def graph_arrays(cfg: dict):
    """The configuration's graph and labels, ``(rowptr, col, labels)``,
    made from ``graph_seed`` once a checkout (``col`` kept as int32)."""
    n = cfg["n_vertices"]
    path = harness.DATA_DIR / (f"graph-{n}-d{cfg['avg_degree']}"
                               f"-z{cfg['skew']}-c{cfg['n_classes']}"
                               f"-s{cfg['graph_seed']}")

    def build(tmp):
        rowptr, col = harness.synth_graph(n, cfg["avg_degree"], cfg["skew"],
                                          cfg["graph_seed"])
        np.save(tmp / "rowptr.npy", rowptr)
        np.save(tmp / "col.npy", col.astype(np.int32))
        np.save(tmp / "labels.npy", harness.labels_for(
            n, cfg["n_classes"], cfg["graph_seed"]))
    _build_once(path, build)
    rowptr = np.load(path / "rowptr.npy")
    col = np.load(path / "col.npy").astype(np.int64)
    labels = np.load(path / "labels.npy")
    for a in (rowptr, col, labels):
        a.flags.writeable = False
    return rowptr, col, labels


def make_data(cfg: dict) -> Data:
    import time

    from repro.gnn.graph import CSRGraph

    t = {}
    t0 = time.perf_counter()
    rowptr, col, labels = graph_arrays(cfg)
    t["graph_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    store = feature_table(cfg)
    t["features_s"] = time.perf_counter() - t0
    graph = CSRGraph(rowptr, col, labels=labels, n_classes=cfg["n_classes"])
    return Data(rowptr, col, labels, graph, store, t)


def init_params(arch, cfg: dict, seed: int):
    """The weights, made on the device in one jitted call from the seed."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), 4)
    return jax.jit(lambda k: arch.init_params(k, cfg))(key)


def system_kwargs(cfg: dict) -> dict:
    """The configuration's settings of the program, as both the trainer's
    and the server's config take them."""
    sysc = cfg["system"]
    return dict(model=cfg["arch"], hidden=cfg["hidden"],
                fanouts=tuple(cfg["fanouts"]), mode=sysc["mode"],
                device_cache_frac=sysc["device_cache_frac"],
                host_cache_frac=sysc["host_cache_frac"],
                cache_policy=sysc["cache_policy"], chaos=None)
