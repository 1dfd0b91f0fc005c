"""What every cell of the on-chip benchmark shares.

Finding files by name (configurations, traffic mixes, per-layer metric
readers, references), the device check, the persistent compile cache,
compile counting, the seeded inputs (graph, features, labels) and the
result line.  Nothing here imports the system under test: the modules in
``bench/kinds/`` do, and the references in ``bench/refs/`` never do.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import threading
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
DATA_DIR = ROOT / ".bench_data"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- files
def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    return load_json(root / find(bench["configs"], name, "config")["file"])


def load_traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def load_module(path: Path):
    """Import a file by path; names may hold dots (``sample_ms.train``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """A per-layer metric's reader: ``LAYER`` and ``read(ctx)``, from
    ``metrics/<name>.py`` or, where the metric has no file of its own,
    from the file of its base name (``compiles.train`` ->
    ``metrics/compiles.py``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path)


def load_ref(arch: str):
    """The plain reference of one architecture (``bench/refs/<arch>.py``)."""
    return load_module(BENCH / "refs" / f"{arch}.py")


def load_kind(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a cell reports: its end-to-end metrics with ``trace``
    off, its per-layer metrics with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------- device
def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not allow_cpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found {info}")
    return info


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileCounter:
    """Counts XLA compilations and their seconds (``jax.monitoring``)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self._lock = threading.Lock()

    def _on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        return False

    def snapshot(self) -> tuple:
        with self._lock:
            return self.compiles, self.compile_s


# ---------------------------------------------------------------- inputs
def synth_graph(n_vertices: int, avg_degree: int, skew: float, seed: int):
    """Power-law CSR graph: vertex v's popularity ~ (rank + 1)^-skew over a
    random permutation; out-degrees are a multinomial draw of
    ``n_vertices * avg_degree`` edges, and so are the endpoints' counts,
    laid out in a random order (iid draws by popularity).  Returns
    read-only ``(rowptr, col)``."""
    rng = np.random.default_rng([seed, 1])
    n_edges = n_vertices * avg_degree
    ranks = rng.permutation(n_vertices)
    pop = (ranks + 1.0) ** (-skew)
    pop /= pop.sum()
    deg = rng.multinomial(n_edges, pop)
    rowptr = np.zeros(n_vertices + 1, np.int64)
    np.cumsum(deg, out=rowptr[1:])
    ends = np.repeat(np.arange(n_vertices, dtype=np.int32),
                     rng.multinomial(n_edges, pop))
    rng.shuffle(ends)
    col = ends.astype(np.int64)
    for a in (rowptr, col):
        a.flags.writeable = False
    return rowptr, col


def labels_for(n_vertices: int, n_classes: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 2]).integers(0, n_classes, n_vertices)


FEATURE_CHUNK = 1 << 15


def feature_fn(row_dim: int):
    """Jitted ``(key, ids) -> rows``: row ``v`` is standard normal float32
    drawn from ``fold_in(key, v)``, so any row can be made again alone."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rows(key, ids):
        def one(i):
            return jax.random.normal(jax.random.fold_in(key, i), (row_dim,),
                                     jnp.float32)
        return jax.vmap(one)(ids)
    return rows


def feature_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed), 3)


def feature_rows(seed: int, row_dim: int, ids: np.ndarray,
                 fn=None) -> np.ndarray:
    """The features of ``ids`` as the set-up wrote them (host float32)."""
    fn = fn or feature_fn(row_dim)
    key = feature_key(seed)
    ids = np.asarray(ids, np.int32)
    out = np.empty((len(ids), row_dim), np.float32)
    for a in range(0, len(ids), FEATURE_CHUNK):
        part = ids[a:a + FEATURE_CHUNK]
        pad = np.zeros(FEATURE_CHUNK, np.int32)
        pad[:len(part)] = part
        out[a:a + len(part)] = np.asarray(fn(key, pad))[:len(part)]
    return out


def write_features(store, seed: int) -> None:
    """Fill a fresh ``FeatureStore`` through ``write_rows``, chunk by
    chunk, the next chunk's rows made on the device while this one is
    written."""
    fn = feature_fn(store.row_dim)
    key = feature_key(seed)
    n = store.n_rows
    starts = list(range(0, n, FEATURE_CHUNK))

    def launch(a):
        ids = np.arange(a, a + FEATURE_CHUNK, dtype=np.int32)
        out = fn(key, ids)
        out.copy_to_host_async()
        return out

    nxt = launch(starts[0])
    for i, a in enumerate(starts):
        cur = nxt
        if i + 1 < len(starts):
            nxt = launch(starts[i + 1])
        b = min(n, a + FEATURE_CHUNK)
        store.write_rows(np.arange(a, b), np.asarray(cur)[:b - a],
                         dedupe=False)


# ---------------------------------------------------------------- numbers
def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries stand for missing
    requests."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))
    return v[k]


def metric(value, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def read_metrics(bench: dict, cell: str, trace: bool, ctx: dict) -> dict:
    """Every metric of the cell that its reader finds something for."""
    out = {}
    for m in cell_metrics(bench, cell, trace):
        if trace:
            val = load_metric(m["name"]).read(ctx)
        else:
            val = ctx["end_to_end"].get(m["name"])
        if val is not None and math.isfinite(val):
            out[m["name"]] = metric(val, m["unit"])
    return out


def print_result(res: dict) -> None:
    """The compared numbers, each beside its limit, as the last lines of
    stderr; then the result as the last line of stdout, with the checks
    last."""
    checks = res.pop("checks", {})
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    res["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(res), flush=True)


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def tmp_root() -> str:
    import tempfile

    return tempfile.mkdtemp(prefix="helios_bench_",
                            dir=os.environ.get("TMPDIR"))


class Spec:
    """One run: the cell's configuration and mix, and the arguments."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 trace: bool, device: dict, t_start: float):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device, self.t_start = device, t_start

    def limits(self) -> dict:
        return self.cfg["limits"][self.traffic["kind"]]


def within(checks: dict) -> bool:
    """Every compared number at or under its limit (NaN fails)."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def execute(bench: dict, cell: dict, cfg: dict, seed: int, seconds: float,
            trace: bool, device: dict, t_start: float):
    """One run of ``cell`` with configuration ``cfg`` on ``device``: the
    cell kind's set-up, window and check.  Returns the result line's
    fields and ``readings(dot, half=False)``, the compared numbers with
    the reference at another precision (or a planted half-batch fault)."""
    import jax

    traffic = load_traffic(cell["traffic"])
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    spec = Spec(cfg, traffic, seed, seconds, trace, device, t_start)
    res = load_kind(traffic["kind"]).run(spec)
    out = {"correct": bool(res["correct"]),
           "attempted": res.get("attempted", 0),
           "failed": res.get("failed", 0)}
    ctx = res.get("ctx", {})
    ctx["end_to_end"] = res.get("end_to_end", {})
    out["metrics"] = read_metrics(bench, cell["name"], trace, ctx)
    dev = dict(device, memory_peak_bytes=res.get("memory_peak_bytes"))
    tr = ctx.get("trace")
    if trace and tr:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["device"] = dev
    out["timings"] = res.get("timings", {})
    out["checks"] = res["checks"]
    return out, res["readings"]
