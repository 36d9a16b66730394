"""One run of one benchmark cell, driven by the files under ``bench/``.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``: the deployment's data, build, storage and
data plane) and a traffic mix (``traffic/<traffic>.json``: the clients
of the closed loop and the search effort). A run:

1. takes the configuration's vectors and queries (``data.py``), and
   the batches in the order the seed draws;
2. builds the index with the program's ``build_pag``, from the
   configuration's ``index_seed``, and writes the partitions with
   ``write_partitions`` into the simulated object store, as many
   replicas as it states;
3. warms up: one pass over the whole query set in the window's own
   micro-batches, which compiles every shape the window will use;
4. runs the window: a closed loop of ``clients`` queries submitted one by
   one through ``AnnsFrontend`` and flushed as one micro-batch, the same
   batches again and again until ``seconds`` are up (the batch in flight
   at the deadline finishes inside the window);
5. checks what the window returned against the references
   (``reference.py``) and reads the metrics named in ``BENCHMARK.json``,
   each through its own reader ``metrics/<metric>.py``.

The program is imported from ``<checkout>/src``; the benchmark hands it
only the generated inputs, in the configuration's ``dtype``, and the
configuration's settings. A configuration or traffic mix that states
what the harness does not honour is refused before any work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SAMPLED_LAUNCHES = 2    # launches of each kernel kept for the parity check
METRICS = ("squared_l2",)   # the only metric reference.py computes
STORAGE_KEYS = {"preset", "n_shards", "replicas"}
TRAFFIC_KEYS = {"name", "arrivals", "clients", "search", "why",
                "recall_at_10_min"}

for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import costs  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import devtrace  # noqa: E402

# program calls wrapped in host spans in traced runs: (module, attribute)
SPANS = (("repro.core.distributed", "ShardedServing.search"),
         ("repro.core.search", "greedy_search"),
         ("repro.core.search", "probe_orders"),
         ("repro.dataplane.wave", "WaveScheduler.run_coalesced"),
         ("repro.dataplane.scan", "ScanStage.topk"),
         ("repro.dataplane.scan", "ScanStage.adc_select"))


def read_json(rel: str) -> dict:
    return json.loads((BENCH / rel).read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int


def load_cell(name: str) -> Cell:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no workload {name!r} under {path.parent}")
    w = json.loads(path.read_text())
    return Cell(name, read_json(f"configs/{w['config']}.json"),
                read_json(f"traffic/{w['traffic']}.json"), int(w["chips"]))


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however fast it compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@contextlib.contextmanager
def count_compiles():
    """Executables built inside the block: ``with count_compiles() as c``,
    then ``c[0]``, and the seconds their compiles took, ``c[1]``."""
    import jax
    count = [0, 0.0]

    def listener(event, duration_s, **_):
        if event == COMPILE_EVENT:
            count[0] += 1
            count[1] += duration_s

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# ------------------------------------------------------------ deployment
def refuse_unhonoured(config: dict) -> None:
    """ValueError where the configuration states what the harness would
    not honour: a metric other than squared L2, a dtype the generator
    does not make, or a storage setting it does not pass on."""
    name = config.get("name")
    if config.get("metric") not in METRICS:
        raise ValueError(f"config {name!r}: metric {config.get('metric')!r}"
                         f" has no reference; the benchmark checks "
                         f"{list(METRICS)} only")
    if config.get("dtype") not in data.DTYPES:
        raise ValueError(f"config {name!r}: dtype {config.get('dtype')!r};"
                         f" the benchmark generates {list(data.DTYPES)} only")
    unknown = set(config["storage"]) - STORAGE_KEYS
    if unknown:
        raise ValueError(f"config {name!r}: storage knows no "
                         f"{sorted(unknown)}; known: {sorted(STORAGE_KEYS)}")


@dataclasses.dataclass
class Deployment:
    base: np.ndarray
    queries: np.ndarray
    serving: object          # repro.core.distributed.ShardedServing
    store: object            # repro.storage.simulator.ObjectStore
    timings: Dict[str, float]


def deploy(config: dict, seeds: Dict[str, int],
           compression: Optional[str] = None) -> Deployment:
    """Data, index build and partition write, as the configuration says:
    the same index for every run seed, which draws only the store's
    latencies. ``compression`` overrides the plane's payloads ("pq"
    writes the float residuals and the PQ codes, so one deployment
    serves both). The vectors reach the program in the configuration's
    dtype."""
    refuse_unhonoured(config)
    from repro.core.distributed import ShardedServing
    from repro.core.pag import build_pag
    from repro.core.search import write_partitions
    from repro.storage.simulator import ObjectStore, StorageConfig
    t0 = time.perf_counter()
    base, queries = data.vectors(config)
    t1 = time.perf_counter()
    with count_compiles() as build_c:
        pag = build_pag(base, seed=config["index_seed"], **config["build"])
    t2 = time.perf_counter()
    st, plane = config["storage"], config["plane"]
    store = ObjectStore(StorageConfig.preset(st["preset"],
                                             seed=seeds["store"]))
    with count_compiles() as write_c:
        write_partitions(pag, base, store, n_shards=st["n_shards"],
                         replicas=st["replicas"],
                         compression=compression or plane["compression"],
                         pq_m=plane.get("pq_m", 8),
                         pq_seed=config["index_seed"])
    t3 = time.perf_counter()
    srv = ShardedServing(pag=pag, store=store, n_shards=st["n_shards"],
                         replicas=st["replicas"], dim=config["d"])
    return Deployment(base, queries, srv, store,
                      {"data_s": t1 - t0, "build_s": t2 - t1,
                       "build_compiles": build_c[0],
                       "build_compile_s": build_c[1],
                       "write_s": t3 - t2, "write_compiles": write_c[0],
                       "write_compile_s": write_c[1],
                       "n_parts": int(pag.n_parts),
                       "graph_rows": int(pag.pg.m_cap)})


def search_config(config: dict, traffic: dict):
    from repro.core.search import SearchConfig
    s, plane = traffic["search"], config["plane"]
    return SearchConfig(L=s["L"], k=config["k"],
                        n_probe_max=s["n_probe_max"],
                        replicas=config["storage"]["replicas"],
                        compression=plane["compression"],
                        pq_m=plane.get("pq_m", 8),
                        rerank_k=plane.get("rerank_k", 32))


# ---------------------------------------------------------------- window
@dataclasses.dataclass
class Window:
    q_idx: np.ndarray        # [N] query of each answer
    ids: np.ndarray          # [N, k]
    d2: np.ndarray           # [N, k]
    latency_s: np.ndarray    # [N] submit -> result on the host
    batch_s: np.ndarray      # [n_batches] first submit -> flush returned
    wall_s: float
    n_batches: int
    probes: int              # SearchStats.n_probes summed
    gets: int                # ObjectStore.n_gets delta
    bytes: int               # ObjectStore.bytes_fetched delta
    compiles: int
    compile_s: float


def batches_of(n_queries: int, clients: int) -> List[np.ndarray]:
    return [np.arange(s, min(s + clients, n_queries))
            for s in range(0, n_queries, clients)]


def run_batches(fe, queries: np.ndarray, batches: List[np.ndarray],
                seconds: Optional[float], span: Callable = None) -> Window:
    """Closed loop: submit each batch's queries one by one, flush, wait
    for every answer on the host, go on with the next batch. With
    ``seconds`` None one pass over ``batches``; otherwise the batches
    cycle until ``seconds`` have passed (at least one batch runs)."""
    span = span or (lambda name: contextlib.nullcontext())
    store = fe.serving.store
    gets0, bytes0 = store.n_gets, store.bytes_fetched
    q_idx, ids, d2, lat, batch_s = [], [], [], [], []
    probes = n = 0
    with count_compiles() as compiles, span(devtrace.WINDOW):
        t0 = time.perf_counter()
        deadline = t0 + (seconds or 0.0)
        while True:
            b = batches[n % len(batches)]
            with span(devtrace.BATCH):
                submitted = []
                for qi in b:
                    submitted.append((fe.submit(queries[qi]),
                                      time.perf_counter()))
                fe.flush()
                done = time.perf_counter()
            batch_s.append(done - submitted[0][1])
            for qi, (ticket, t_sub) in zip(b, submitted):
                r_ids, r_d2, _ = fe.results.pop(ticket)
                q_idx.append(qi)
                ids.append(np.asarray(r_ids, np.int64))
                d2.append(np.asarray(r_d2, np.float32))
                lat.append(done - t_sub)
            probes += int(np.sum(fe.last_stats.n_probes))
            n += 1
            if seconds is None:
                if n == len(batches):
                    break
            elif time.perf_counter() >= deadline:
                break
        wall = time.perf_counter() - t0
    return Window(np.asarray(q_idx), np.stack(ids), np.stack(d2),
                  np.asarray(lat), np.asarray(batch_s), wall, n, probes,
                  store.n_gets - gets0, store.bytes_fetched - bytes0,
                  compiles[0], compiles[1])


class Capture:
    """Wraps the program's scan kernels (``repro.kernels.ops``) while
    installed: records every launch's shape as ``costs.KERNELS`` take it
    (the pool's or codes' shape, k, the itemsize of the pool or codes and
    of the query side: queries or tables), and keeps a sample of
    ``keep`` launches per kernel (inputs and outputs), drawn from the
    seed by reservoir sampling over the launches as they come."""

    def __init__(self, seed: int, keep: int = SAMPLED_LAUNCHES):
        self.rng = np.random.default_rng(seed)
        self.keep = keep
        self.shapes: Dict[str, List[tuple]] = {k: [] for k in costs.KERNELS}
        self.sample: Dict[str, List[tuple]] = {k: [] for k in costs.KERNELS}

    def _record(self, name, args, kw, out):
        k = kw.get("k", 10)
        self.shapes[name].append(tuple(args[1].shape) + (
            k, args[1].dtype.itemsize, args[0].dtype.itemsize))
        seen = len(self.shapes[name])
        if seen <= self.keep:
            self.sample[name].append((args, kw, out))
        else:
            j = int(self.rng.integers(0, seen))
            if j < self.keep:
                self.sample[name][j] = (args, kw, out)

    @contextlib.contextmanager
    def installed(self):
        from repro.kernels import ops
        orig = {name: getattr(ops, name) for name in costs.KERNELS}

        def wrap(name, fn):
            def launch(*args, **kw):
                out = fn(*args, **kw)
                self._record(name, args, kw, out)
                return out
            return launch

        for name, fn in orig.items():
            setattr(ops, name, wrap(name, fn))
        try:
            yield self
        finally:
            for name, fn in orig.items():
                setattr(ops, name, fn)


@contextlib.contextmanager
def host_spans(names=SPANS):
    """Wrap each named program call in a ``TraceAnnotation`` of its
    name. A name that is not there is skipped and listed in the yielded
    ``missing``."""
    import jax
    undo, missing = [], []
    for mod_name, attr in names:
        try:
            owner = importlib.import_module(mod_name)
            *path, leaf = attr.split(".")
            for part in path:
                owner = getattr(owner, part)
            fn = getattr(owner, leaf)
        except (ImportError, AttributeError):
            missing.append(attr)
            continue

        def wrapped(*a, __fn=fn, __name=attr, **kw):
            with jax.profiler.TraceAnnotation(__name):
                return __fn(*a, **kw)

        setattr(owner, leaf, wrapped)
        undo.append((owner, leaf, fn))
    try:
        yield missing
    finally:
        for owner, leaf, fn in reversed(undo):
            setattr(owner, leaf, fn)


# ----------------------------------------------------------------- check
def check(base: np.ndarray, queries: np.ndarray, win: Window,
          capture: Capture, k: int, limits: Dict[str, float],
          recall_min: float) -> Dict[str, dict]:
    """Every answer of the window against the references. Returns each
    number compared with its limit, ``bound`` saying which side of the
    limit is good, and ``ok``."""
    n = len(base)
    in_range = (win.ids >= 0) & (win.ids < n)
    full = in_range.all(axis=1) & np.array(
        [len(set(r.tolist())) == k for r in win.ids])
    ref = reference.answer_d2(base, queries, win.q_idx, win.ids)
    rel = np.abs(win.d2.astype(np.float64) - ref) / np.maximum(ref, 1e-30)
    d2_gap = float(rel[in_range].max(initial=0.0)) if in_range.any() \
        else float("inf")
    gt = reference.exact_knn(base, queries, k)
    out = {
        "answers_failed": (int((~full).sum()), 0, "max"),
        "d2_gap": (d2_gap, limits["d2_gap"], "max"),
        "recall_at_10": (reference.recall(base, queries, win.q_idx, win.ids,
                                          gt[win.q_idx], k),
                         recall_min, "min"),
    }
    pools = {"l2_topk_masked": reference.pool_d2_l2,
             "pq_adc_masked": reference.pool_d2_pq}
    for name, launches in capture.sample.items():
        if not launches:
            continue
        gap = 0.0
        for args, kw, (out_d, out_i) in launches:
            ref_d2 = np.asarray(pools[name](args[0], args[1]))
            gap = max(gap, reference.kernel_gap(
                ref_d2, np.asarray(args[2]), np.asarray(out_d),
                np.asarray(out_i)))
        out[f"{name}_gap"] = (gap, limits[f"{name}_gap"], "max")
    return {name: {"value": v, "limit": lim, "bound": b,
                   "ok": bool(v <= lim if b == "max" else v >= lim)}
            for name, (v, lim, b) in out.items()}


# --------------------------------------------------------------- metrics
def metric_entries(cell: str, kind: str) -> List[dict]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics of
    BENCHMARK.json that this cell reports."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, ctx: dict) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def recall_floor(config: dict, traffic: dict) -> float:
    """The least ``recall_at_10`` a run may read: the traffic mix's own
    floor where it states one (a lower search effort than the
    configuration's floor was set at), else the configuration's."""
    return traffic.get("recall_at_10_min",
                       config["guarantees"]["recall_at_10_min"])


# ------------------------------------------------------------------- run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Optional[dict] = None, log=print) -> dict:
    """One run; returns the result line's object. ``t_start`` is the
    process's start on the host clock (set-up runs from it to the first
    query of the window). ``peaks`` (``costs.peaks``) feeds the roofline
    readers."""
    seeds = data.sub_seeds(seed)
    return run_deployed(cell, deploy(cell.config, seeds), seeds, seconds,
                        trace, t_start, peaks=peaks, log=log)


def run_deployed(cell: Cell, dep: Deployment, seeds: Dict[str, int],
                 seconds: Optional[float], trace: bool, t_start: float,
                 peaks: Optional[dict] = None,
                 swap: Optional[Callable] = None, log=print) -> dict:
    """``run_cell`` from the warm-up on, on a deployment made already.
    ``swap``, a context manager factory, is entered before the warm-up
    and left after the window: the control and the fault tests put their
    version of the program in place there."""
    import jax
    config, traffic = cell.config, cell.traffic
    unknown = set(traffic) - TRAFFIC_KEYS
    if traffic["arrivals"] != "closed" or unknown:
        raise ValueError(f"traffic {traffic['name']!r}: this generator runs "
                         f"closed loops only, and knows no {sorted(unknown)}")
    log(json.dumps({"phase": "deploy", **dep.timings}))
    scfg = search_config(config, traffic)
    batches = batches_of(len(dep.queries), traffic["clients"])
    batches = [batches[i]
               for i in data.batch_order(len(batches), seeds["batches"])]

    from repro.serving.engine import AnnsFrontend
    capture = Capture(seeds["sample"])
    tr, missing = None, []
    with (swap() if swap else contextlib.nullcontext()):
        t_warm = time.perf_counter()
        warm = run_batches(AnnsFrontend(dep.serving, scfg,
                                        max_batch=traffic["clients"]),
                           dep.queries, batches, None)
        log(json.dumps({"phase": "warmup", "s": time.perf_counter() - t_warm,
                        "batches": warm.n_batches,
                        "compiles": warm.compiles,
                        "compile_s": warm.compile_s}))
        fe = AnnsFrontend(dep.serving, scfg, max_batch=traffic["clients"])
        setup_s = time.perf_counter() - t_start
        if trace:
            log_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(log_dir), profiler_options=opts)
            try:
                with host_spans() as missing, capture.installed():
                    win = run_batches(fe, dep.queries, batches, seconds,
                                      span=jax.profiler.TraceAnnotation)
            finally:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
            t_load = time.perf_counter()
            tr = devtrace.load(log_dir, {attr for _, attr in SPANS})
            shutil.rmtree(log_dir, ignore_errors=True)
            log(json.dumps({"phase": "trace", "stop_s": t_load - t_stop,
                            "load_s": time.perf_counter() - t_load}))
        else:
            with capture.installed():
                win = run_batches(fe, dep.queries, batches, seconds)
    log(json.dumps({"phase": "window", "s": win.wall_s,
                    "batches": win.n_batches, "queries": len(win.q_idx),
                    "compiles": win.compiles, "spans_missing": missing,
                    "batch_s": win.batch_s.tolist()}))

    devices = jax.devices()[:cell.chips]
    mem = [d.memory_stats() or {} for d in devices]
    peak_mem = max(m.get("peak_bytes_in_use", 0) for m in mem)
    log(json.dumps({"phase": "memory", "peak_bytes": peak_mem,
                    "in_use_serving_bytes": max(m.get("bytes_in_use", 0)
                                                for m in mem)}))
    # the program's state goes before the references run
    base, queries = dep.base, dep.queries
    del dep, fe
    gc.collect()

    t_check = time.perf_counter()
    limits = read_json("limits.json")["limits"]
    checks = check(base, queries, win, capture, config["k"], limits,
                   recall_floor(config, traffic))
    log(json.dumps({"phase": "check", "s": time.perf_counter() - t_check}))
    # what a reader in metrics/ may read
    ctx = {"setup_s": setup_s, "window": win, "trace": tr,
           "launches": capture.shapes, "peaks": peaks, "checks": checks}
    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in metric_entries(cell.name, kind):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_mem}
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": int(len(win.q_idx)),
              "failed": int(checks["answers_failed"]["value"]),
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_by_host(10)}
    result["checks"] = {name: {"value": c["value"], "limit": c["limit"],
                               "bound": c["bound"]}
                        for name, c in checks.items()}
    return result
