#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json`` on the chips of this
machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It makes the model's weights and the
kNN-LM datastore on the device from ``--seed``, stands the engine up
behind the in-process HTTP gateway, warms up every program the run's
requests use, then drives the gateway from client coroutines over
loopback for ``--seconds`` seconds. After the window it compares what was
served with the plain reference (``check.py``) and prints one JSON line:
``correct``, ``attempted``, ``failed``, the cell's metrics, the device,
and last ``checks``, each compared number beside its limit. With
``--trace 1`` the window is traced by the profiler and the metrics are
the cell's per-layer ones.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the names in ``BENCHMARK.json``: the
configuration's ``file``, the LM module it names
(``bench/lm/<lm>.py``), ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py`` (see ``bench/README.md``).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

NO_CHIP = 3
TRACE_DIR = HERE / ".traces"
#: keys of a configuration's ``model`` block that are the harness's own,
#: not the engine's: the weights' scale and the LM module's name
HARNESS_MODEL_KEYS = ("init_scale", "lm")


def log(**fields) -> None:
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices; raises ``NoChip`` on any other
    platform or with fewer chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:n]


def load_cell(root: pathlib.Path, workload: str):
    """The cell's entry, configuration, traffic mix and LM module, found
    under ``root``. A configuration the harness cannot serve (an LM
    module with no file, a ``model`` key the engine does not take, a
    model block its LM module refuses) is refused here, before any
    device work."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    lm = lm_module(root, cfg["model"].get("lm", "dense"))
    model_config(cfg)
    try:
        lm.Model.from_config(cfg["model"])
    except ValueError as e:
        raise SystemExit(f"configuration {cfg['name']!r}: {e}") from None
    return bench, cell, cfg, traffic, lm


def lm_module(root: pathlib.Path, name: str):
    """The LM module ``<root>/bench/lm/<name>.py`` (see the docstring of
    ``bench/lm/dense.py`` for what it provides)."""
    path = (root / "bench" / "lm" / f"{name}.py").resolve()
    if not path.is_file():
        raise SystemExit(f"the configuration names the LM {name!r}, but "
                         f"there is no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_lm_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` from every key of the configuration's
    ``model`` block but the harness's own (JSON lists as tuples), named
    as the configuration is. A key it has no field for raises."""
    from repro.models.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    keys = {k: v for k, v in cfg["model"].items()
            if k not in HARNESS_MODEL_KEYS}
    unknown = sorted(set(keys) - fields)
    if unknown:
        raise SystemExit(f"configuration {cfg['name']!r}: the engine's "
                         f"ModelConfig takes no model key {unknown}")
    return ModelConfig(name=cfg["name"], **{
        k: tuple(v) if isinstance(v, list) else v for k, v in keys.items()})


def cell_metrics(bench: Dict, cell: Dict, trace: bool) -> List[Dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}",
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts the programs JAX builds or loads from its cache, and the
    seconds that took."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        self.names: Dict[str, int] = {}
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._miss)

    def _miss(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _event(self, event: str, duration: float, fun_name: str = "?",
               **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration
            self.names[fun_name] = self.names.get(fun_name, 0) + 1


def compile_cache() -> None:
    """JAX's persistent compilation cache, where the program puts it (the
    checkout's ``.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
    otherwise), keeping every program however quick to build: the engine
    builds thousands of small ones (its eager per-wave work, once per
    wave size), and a run after the first loads them instead. Eviction is
    off: with a size bound JAX scans the whole directory on every write,
    which over thousands of entries stalls set-up for minutes."""
    import jax
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def build_engine(cfg: Dict, params, tables, summary: Dict):
    """The program under test, stood up as a deployment would: the
    service-backed retriever, no per-stage blocking, a fixed KV pool."""
    from repro.core.ivfpq import IVFPQConfig, IVFPQParams, IVFPQShard
    from repro.core.rag import RagConfig
    from repro.serve import EngineConfig, RalmEngine
    from repro.serve.datastore import Datastore

    ds, eng, rag = cfg["datastore"], cfg["engine"], cfg["rag"]
    model = model_config(cfg)
    icfg = IVFPQConfig(dim=model.d_model, nlist=ds["nlist"], m=ds["m"],
                       nbits=8, residual=False, list_cap=summary["list_cap"])
    store = Datastore(
        params=IVFPQParams(tables.centroids, tables.codebooks),
        shards=[IVFPQShard(tables.codes, tables.ids, tables.lens)],
        index_cfg=icfg, payload_tokens=tables.payload,
        num_vectors=ds["vectors"])
    econf = EngineConfig(
        model=model, rag=RagConfig(mode=rag["mode"], interval=rag["interval"],
                                   k=rag["k"], lam=rag["lam"],
                                   temperature=rag["temperature"]),
        max_seq=eng["max_seq"], kv_slots=eng["kv_slots"],
        async_retrieval=True, retrieval_measure=False,
        attn_seq_block=eng["attn_seq_block"])
    return RalmEngine.from_config(econf, params, store,
                                  store.search_config(nprobe=ds["nprobe"],
                                                      k=rag["k"]))


def annotate(engine, recorder) -> None:
    """Host spans (``bench.*``) around the engine's calls into each layer,
    written into the profiler's trace: what the host was doing while the
    chip sat idle."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, attr, name):
        inner = getattr(obj, attr)

        def call(*a, **kw):
            with TraceAnnotation(name):
                return inner(*a, **kw)
        setattr(obj, attr, call)

    wrap(engine.scheduler, "step", "bench.step")
    wrap(engine.backend, "prefill", "bench.prefill")
    wrap(engine.backend, "decode_wave", "bench.decode_wave")
    wrap(engine, "finish_wave", "bench.finish_wave")
    wrap(recorder, "flush", "bench.search_flush")


def warm(engine, plan, eng: Dict, vocab: int, compiles) -> None:
    """Build every program the run's requests (``plan``) use before the
    window:

    * the prefill of every prompt length of the plan, and every wave size
      1..``kv_slots`` of the per-wave work sized to the exact number of
      rows (the retrieval wave among it), by serving the rounds of
      ``loadgen.warmup``;
    * the decode wave of every (pow2 wave bucket, attention length) pair
      the plan can reach, by running the engine's decode program once
      each on the pool's scratch slot;
    * the program the engine runs when a request completes, one per
      (prompt length, output length) pair (it joins the prompt and the
      tokens with ``jnp.concatenate``), by making the same call.
    """
    import jax
    import jax.numpy as jnp
    import loadgen
    from repro.serve import RalmRequest
    rid = 0
    for i, rnd in enumerate(loadgen.warmup(plan, eng["kv_slots"],
                                           eng["max_seq"], vocab)):
        for r in rnd:
            rid -= 1     # ids below zero: the gateway numbers from zero
            engine.submit(RalmRequest(prompt=jnp.asarray([r.prompt],
                                                         jnp.int32),
                                      steps=r.max_tokens, request_id=rid))
        engine.run()
        log(phase="warm", round=i, requests=len(rnd),
            s=time.perf_counter() - T_START, programs=compiles.count)
    pool = engine.pool
    for bucket, kv_len in loadgen.decode_shapes(plan, eng["kv_slots"],
                                                eng["max_seq"],
                                                eng["attn_seq_block"]):
        slots = jnp.full((bucket,), pool.scratch, jnp.int32)
        _, pool.caches, _ = engine.backend.decode_wave(
            pool.caches, jnp.zeros((bucket, 1), jnp.int32), slots,
            jnp.zeros((bucket,), jnp.int32), kv_len=kv_len,
            attn_spec=engine.attn_spec)
    log(phase="warm", round="decode", s=time.perf_counter() - T_START,
        programs=compiles.count)
    for t0, n in loadgen.length_pairs(plan):
        jnp.concatenate([jnp.zeros((1, t0), jnp.int32)]
                        + [jnp.zeros((1, 1), jnp.int32)] * n, axis=1)
    jax.effects_barrier()


class Context:
    """What a metric reader gets: the configuration and its LM module
    (``lm``), the run's requests and window, the server's counters over
    it, and with ``--trace 1`` the reduced trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def tokens(self, lo: float, hi: float):
        """(result, token index, arrival time) of every token that reached
        its client in [lo, hi]."""
        return [(r, j, t) for r in self.results
                for j, t in enumerate(r.token_times) if lo <= t <= hi]


class Server:
    """One seed's weights and datastore, made with the configuration's LM
    module ``lm``, the engine over them behind the HTTP gateway, warmed
    up for the requests of ``plan``."""

    def __init__(self, cfg: Dict, lm, traffic: Dict, seed: int, compiles,
                 plan, annotated: bool = False):
        import jax

        import check
        import datastore
        import reference as ref
        from repro.serve import Gateway, GatewayConfig

        self.cfg, self.lm, self.traffic, self.seed = cfg, lm, traffic, seed
        self.model = lm.Model.from_config(cfg["model"])
        eng = cfg["engine"]
        k_w, k_d = jax.random.split(ref.seed_key(seed))
        self.params = lm.make_weights(k_w, self.model)
        self.tables, summary = datastore.build(lm, self.params, self.model,
                                               cfg["datastore"], k_d)
        jax.block_until_ready(self.tables)
        log(phase="datastore", s=time.perf_counter() - T_START, **summary)
        self.engine = build_engine(cfg, self.params, self.tables, summary)
        self.recorder = check.Recorder(self.engine.retriever)
        self.engine.retriever = self.recorder
        self.recorder.attach(self.engine)
        if annotated:
            annotate(self.engine, self.recorder)
        warm(self.engine, plan, eng, self.model.vocab_size, compiles)
        log(phase="warm", s=time.perf_counter() - T_START,
            programs=compiles.count, program_s=compiles.seconds)
        self.gateway = Gateway(self.engine, GatewayConfig(
            port=0, degrade=None, max_queue_depth=1 << 20,
            max_tokens_cap=max(r.max_tokens for r in plan),
            max_prompt_tokens=eng["max_seq"]))
        self.gateway.start_background()
        self.compiles = compiles

    def window(self, plan, seconds: float,
               trace_dir: Optional[pathlib.Path] = None) -> Dict:
        """Drive the window (and wait for what is still in flight when it
        closes); returns the requests' results and the server's counters
        before and after."""
        import jax

        import client
        import devtrace
        import loadgen
        port = self.gateway.port
        # one request through HTTP first: the gateway's own path
        now = time.perf_counter()
        client.run(port, [loadgen.Request(-1, plan[0].prompt, 2, 0.0)], now)
        built0, missed0 = self.compiles.count, self.compiles.misses
        self.compiles.names = {}
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # host spans and device operations; no Python call tracing
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level, options.python_tracer_level = 2, 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        self.recorder.rows, self.recorder.on = [], True
        t0 = time.perf_counter()
        t1 = t0 + seconds
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            results = client.run(port, plan, t0)
        t_end = time.perf_counter()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        self.recorder.on = False
        built = self.compiles.count - built0
        late = sorted(r.sent - r.due for r in results if r.sent is not None)
        log(phase="generator", requests=len(results),
            late_p50_ms=1e3 * late[len(late) // 2] if late else None,
            late_max_ms=1e3 * late[-1] if late else None)
        log(phase="window", seconds=seconds, drain_s=t_end - t1,
            requests=len(results),
            programs_built=built, built=self.compiles.names,
            cache_misses=self.compiles.misses - missed0)
        return dict(results=results, t0=t0, t1=t1, t_end=t_end,
                    programs_built=built, rows=self.recorder.rows)

    def close(self) -> None:
        """Stop the gateway and drop the engine (its KV pool and its copy
        of the tables), keeping the weights and the datastore."""
        self.gateway.shutdown()
        self.recorder.inner = self.engine = self.gateway = None
        gc.collect()

    def readings(self, results, rows) -> Dict[str, float]:
        """The compared numbers of a window's results (``check.py``)."""
        import check
        sample = check.pick_sample(results, self.traffic["sample_requests"],
                                   self.seed)
        if not sample:
            return {}
        self.reference = check.Reference(
            self.lm, self.params, self.model, self.tables,
            dict(self.cfg["rag"], nprobe=self.cfg["datastore"]["nprobe"]),
            sample, self.cfg["engine"]["max_seq"])
        values = check.readings(self.reference, rows)
        log(phase="check", sample_requests=len(sample),
            sample_tokens=sum(len(r.tokens) for r in sample),
            readings=values)
        return values


def main(argv: Optional[List[str]] = None,
         root: pathlib.Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="PATH",
                    help="also write the reduced trace (JSON) here")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic, lm = load_cell(root, args.workload)
    if cell["chips"] != 1:
        # the engine is stood up monolithic on one chip; a cell on more
        # chips needs a topology (LM and retrieval devices) that
        # build_engine does not read yet
        print(f"bench: {cell['name']} asks for {cell['chips']} chips; the "
              "harness serves one-chip cells only", file=sys.stderr)
        return 2
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    compile_cache()
    compiles = CompileCounter()

    import check
    import devtrace
    import loadgen
    from peaks import lookup

    plan = loadgen.plan(traffic, args.seconds, args.seed,
                        cfg["model"]["vocab_size"])
    server = Server(cfg, lm, traffic, args.seed, compiles, plan,
                    annotated=bool(args.trace))
    w = server.window(plan, args.seconds, TRACE_DIR if args.trace else None)
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)
    server.close()

    peak = lookup(devices[0].device_kind) if devices[0].platform == "tpu" \
        else None
    ctx = Context(cfg=cfg, lm=lm, traffic=traffic,
                  setup_s=w["t0"] - T_START,
                  peak=peak, chips=len(devices),
                  tables=server.tables, trace=None, win=None, planes=[], **w)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {}
    if args.trace:
        ctx.trace = devtrace.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if args.keep_trace:
            devtrace.save(ctx.trace, args.keep_trace)
        ctx.win = devtrace.window(ctx.trace)
        ctx.planes = devtrace.devices(ctx.trace)[:len(devices)]
        if ctx.win and ctx.planes:
            ctx.win = devtrace.aligned(ctx.trace, ctx.planes[0], ctx.win)
            lo, hi = ctx.win
            device["busy_s"] = sum(devtrace.busy_ns(ctx.trace, p, lo, hi)
                                   for p in ctx.planes) \
                / len(ctx.planes) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            out["breakdown"] = {
                "device_ops": [list(x) for x in devtrace.top_ops(
                    ctx.trace, ctx.planes[0], lo, hi)],
                "idle_gaps": [list(x) for x in devtrace.idle_gaps(
                    ctx.trace, ctx.planes[0], lo, hi)]}
    metrics = {}
    for m in cell_metrics(bench, cell, bool(args.trace)):
        value = reader(m["name"])(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    values = server.readings(w["results"], w["rows"])
    ok, checks = check.judge(values, cfg["limits"])
    failed = sum(not r.complete for r in w["results"])
    correct = bool(ok and failed == 0 and values)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": len(w["results"]),
              "failed": failed, "metrics": metrics, "device": device, **out,
              "checks": checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
