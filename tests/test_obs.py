"""The observability plane: tracing, metrics, and their serving wiring.

The load-bearing claims, in test order:

  * a disabled tracer is genuinely free — ``span()`` returns one
    module-level null singleton and the hot path allocates NOTHING in
    ``repro.obs.trace`` (pinned with tracemalloc), so tracing can stay
    compiled into the wave loop;
  * a live tracer is thread-safe and bounded: concurrent spans from
    many threads land exactly once in a ring buffer that drops oldest
    instead of growing, and the export still validates;
  * the export speaks the Chrome trace-event contract — phases, X
    durations, flow-event pairing — checked by ``validate_chrome_trace``
    both positively (our own exports) and negatively (corrupted docs);
  * histogram bucket math follows Prometheus semantics (``le`` is an
    inclusive upper bound, cumulative series, ``+Inf`` == count) and
    reservoir quantiles track known distributions;
  * ``/metricsz`` renders parseable exposition text: valid sample/label
    syntax, one TYPE per family, no duplicate sample names;
  * a request's trace id flows through a REAL scheduler wave — admit,
    decode, search, finish, retrieval stages, KV alloc/release — and
    the flow arrow connects queue-wait to the first-token wave;
  * the same engine with tracing disabled records zero events over the
    same workload (the satellite overhead criterion, structurally).

The HTTP tests share one module-scoped gateway like tests/test_gateway.
"""
import dataclasses
import json
import re
import socket
import threading
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tf
from repro.obs import (DEFAULT_BUCKETS, Histogram, MetricsRegistry,
                       Reservoir, Tracer, validate_chrome_trace)
from repro.obs.trace import NULL_SPAN, NULL_TRACER
from repro.retrieval.stats import RetrievalStats, StageStat
from repro.serve import (DatastoreBuilder, RagConfig, RalmEngine,
                         RalmRequest, ServiceConfig)
from repro.serve.gateway import Gateway, GatewayConfig

# ---------------------------------------------------------------------------
# tracer core (no jax)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_span_nesting_and_export():
    clock = FakeClock(5.0)
    tr = Tracer(clock=clock)
    with tr.span("outer", "wave", args={"rows": 2}):
        clock.t += 0.1
        with tr.span("inner", "wave"):
            clock.t += 0.2
        clock.t += 0.1
    doc = tr.export()
    assert doc["displayTimeUnit"] == "ms"
    assert validate_chrome_trace(doc) == []
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["args"] == {"rows": 2}
    assert outer["ts"] == pytest.approx(0.0)
    assert outer["dur"] == pytest.approx(0.4e6)
    # proper nesting: inner starts after outer and ends before it
    assert inner["ts"] == pytest.approx(0.1e6)
    assert inner["dur"] == pytest.approx(0.2e6)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # the track is announced exactly once
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["args"]["name"] == "wave"
    assert outer["tid"] == inner["tid"] == meta[0]["tid"]


def test_instant_flow_and_retroactive_complete():
    clock = FakeClock(5.0)
    tr = Tracer(clock=clock)
    clock.t = 6.0
    tr.instant("kvpool.alloc", "kvpool", args={"rows": 2})
    tr.flow_start(42, t_s=5.5)
    tr.flow_end(42, track="wave", t_s=6.0)
    tr.complete("queue.wait", "requests", t0_s=5.25, dur_s=0.5)
    tr.complete("clamped", "requests", t0_s=6.0, dur_s=-1.0)
    assert validate_chrome_trace(tr.export()) == []
    evs = {e["name"]: e for e in tr.events() if e["ph"] != "M"}
    inst = evs["kvpool.alloc"]
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert inst["ts"] == pytest.approx(1.0e6)
    assert evs["queue.wait"]["ts"] == pytest.approx(0.25e6)
    assert evs["queue.wait"]["dur"] == pytest.approx(0.5e6)
    assert evs["clamped"]["dur"] == 0.0          # negative dur clamps
    flows = [e for e in tr.events() if e.get("cat") == "flow"]
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert all(e["id"] == 42 for e in flows)
    assert flows[1]["bp"] == "e"                 # bind to enclosing slice


def test_ring_buffer_bounded():
    tr = Tracer(capacity=16)
    for i in range(100):
        tr.instant(f"e{i}", "t")
    evs = tr.events()
    assert len(evs) == 16                        # oldest fell off
    assert evs[-1]["name"] == "e99"              # newest survives


def test_clear_reemits_track_metadata():
    tr = Tracer()
    with tr.span("a", "wave"):
        pass
    with tr.span("b", "retrieval"):
        pass
    tr.clear()
    assert all(e["ph"] == "M" for e in tr.events())
    assert {e["args"]["name"] for e in tr.events()} == {"wave", "retrieval"}
    with tr.span("after", "wave"):
        pass
    doc = tr.export()
    assert validate_chrome_trace(doc) == []      # still self-contained
    assert any(e["name"] == "after" for e in doc["traceEvents"])


def test_tracer_thread_safety():
    tr = Tracer(capacity=1 << 15)
    nthreads, per = 8, 200

    def worker(i):
        track = f"t{i % 4}"
        for j in range(per):
            with tr.span(f"s{i}", track):
                pass
            tr.instant(f"i{i}", track)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    # every event landed exactly once: 4 track announcements plus
    # (span + instant) * per * nthreads
    assert len(evs) == 4 + 2 * per * nthreads
    assert validate_chrome_trace(tr.export()) == []
    assert len({e["tid"] for e in evs}) == 4     # stable track ids


def test_disabled_tracer_is_null_and_silent():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a", args={"x": 1}), tr.span("b")
    assert s1 is s2 is NULL_SPAN                 # one shared singleton
    with s1:
        pass
    tr.instant("i")
    tr.complete("c", "t", 0.0, 1.0)
    tr.flow_start(1)
    tr.flow_end(1)
    assert tr.events() == []
    assert len(NULL_TRACER.events()) == 0        # the module-global too


def test_overhead_guard_disabled_tracer():
    """The disabled hot path must not allocate inside repro.obs.trace:
    that is the mechanism behind the <2%% tokens/s acceptance bound."""
    from repro.obs import trace as trace_mod
    tr = Tracer(enabled=False)

    def hot_loop(n):
        for _ in range(n):
            with tr.span("hot", "wave"):
                pass
            tr.instant("hot", "wave")
            tr.flow_start(7)
            tr.flow_end(7)

    # first traced pass absorbs one-time interpreter caches (attributed
    # to the function bodies in trace.py); the measured pass must then
    # allocate NOTHING — any per-iteration allocation scales to > 0
    tracemalloc.start()
    hot_loop(2000)
    before = tracemalloc.take_snapshot()
    hot_loop(2000)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    filt = [tracemalloc.Filter(True, trace_mod.__file__)]
    diff = after.filter_traces(filt).compare_to(
        before.filter_traces(filt), "lineno")
    assert sum(d.size_diff for d in diff) <= 0, \
        [(d.traceback, d.size_diff) for d in diff if d.size_diff > 0]


def test_validator_rejects_malformed_docs():
    assert validate_chrome_trace({"nope": 1})    # no traceEvents
    assert validate_chrome_trace("text")         # wrong type
    assert validate_chrome_trace([1, 2]) != []   # events must be dicts
    base = {"pid": 1, "tid": 1, "ts": 0.0, "name": "e"}
    assert validate_chrome_trace([{**base, "ph": "Q"}])   # unknown phase
    assert validate_chrome_trace([{**base, "ph": "X"}])   # X without dur
    assert validate_chrome_trace(
        [{**base, "ph": "X", "dur": -5}])                  # negative dur
    assert validate_chrome_trace([{"ph": "i", "ts": 0.0}])  # missing keys
    # flow pairing, both directions
    s = {**base, "ph": "s", "id": 9}
    f = {**base, "ph": "f", "id": 9}
    assert validate_chrome_trace([s]) != []      # start without finish
    assert validate_chrome_trace([f]) != []      # finish without start
    assert validate_chrome_trace([s, f]) == []   # paired: clean
    # a bare event list (no wrapper dict) is accepted
    assert validate_chrome_trace([{**base, "ph": "i"}]) == []


# ---------------------------------------------------------------------------
# metrics core (no jax)
# ---------------------------------------------------------------------------


def test_histogram_bucket_math():
    h = Histogram("t_seconds", "test", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 7.0, 99.0):
        h.observe(v)
    lines = h.render()
    # le is an INCLUSIVE upper bound: 0.1 counts in le="0.1"
    assert 't_seconds_bucket{le="0.1"} 2' in lines
    assert 't_seconds_bucket{le="1"} 4' in lines
    assert 't_seconds_bucket{le="10"} 5' in lines
    assert 't_seconds_bucket{le="+Inf"} 6' in lines
    assert "t_seconds_count 6" in lines
    assert h.count == 6
    assert h.sum == pytest.approx(107.65)
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["sum"] == pytest.approx(107.65)


def test_histogram_quantiles_track_distribution():
    h = Histogram("q_seconds", buckets=DEFAULT_BUCKETS)
    for i in range(1, 1001):
        h.observe(i / 1000.0)                    # uniform on (0, 1]
    assert h.quantile(0.50) == pytest.approx(0.5, abs=0.01)
    assert h.quantile(0.99) == pytest.approx(0.99, abs=0.01)
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(0.5, abs=0.01)
    assert snap["p99"] == pytest.approx(0.99, abs=0.01)


def test_reservoir_bounded_and_uniform():
    r = Reservoir(cap=256)
    for i in range(10_000):
        r.add(float(i))
    assert len(r) == 256 and r.n == 10_000       # bounded, counts all
    # a uniform sample of 0..9999: the median estimate is mid-range
    assert 3000 < r.quantile(0.5) < 7000
    assert Reservoir().quantile(0.5) == 0.0      # empty: defined


_LV = r'"(?:[^"\\\n]|\\.)*"'                             # label value
SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                         # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*=' + _LV +                # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _LV + r')*\})?'       # more labels
    r' (-?\d+(\.\d+)?([eE][-+]?\d+)?|[+-]Inf|NaN)$')     # value


def _check_exposition(text):
    """Prometheus text-format invariants: every sample line parses, one
    TYPE per family, no duplicate sample names."""
    typed, seen = [], []
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            typed.append(line.split()[2])
        elif line and not line.startswith("#"):
            assert SAMPLE_RE.match(line), f"bad sample line: {line!r}"
            seen.append(line.rsplit(" ", 1)[0])
    assert len(typed) == len(set(typed)), "duplicate TYPE declarations"
    assert len(seen) == len(set(seen)), "duplicate sample names"
    return typed, seen


def test_registry_render_is_valid_exposition():
    reg = MetricsRegistry()
    c = reg.counter("ralm_reqs_total", "requests")
    c.inc(3, labels={"tenant": "a"})
    c.inc(1, labels={"tenant": 'quo"te\n'})      # needs escaping
    reg.gauge("ralm_depth", "queue depth").set(5)
    reg.histogram("ralm_lat_seconds", "latency",
                  buckets=(0.1, 1.0)).observe(0.2)
    reg.counter("ralm_empty_total", "never incremented")
    text = reg.render()
    typed, seen = _check_exposition(text)
    assert "ralm_reqs_total" in typed and "ralm_lat_seconds" in typed
    assert "ralm_lat_seconds_p99" in typed       # reservoir companions
    assert 'ralm_reqs_total{tenant="a"} 3' in text.splitlines()
    assert any(s.startswith("ralm_empty_total") for s in seen)


def test_registry_idempotent_and_kind_clash():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a           # get-or-create
    reg.histogram("h_seconds")
    with pytest.raises(TypeError):
        reg.gauge("h_seconds")                   # kind mismatch
    # collectors run at scrape time, not registration time
    hits = []
    reg.register_collector(lambda: hits.append(1))
    assert not hits
    reg.render()
    reg.snapshot()
    assert len(hits) == 2


def test_counter_snapshot_shapes():
    reg = MetricsRegistry()
    plain = reg.counter("plain_total")
    plain.inc(2)
    assert reg.snapshot()["plain_total"] == 2.0  # unlabelled: scalar
    lab = reg.counter("lab_total")
    lab.inc(1, labels={"op": "scan"})
    assert reg.snapshot()["lab_total"] == {'{op="scan"}': 1.0}


# ---------------------------------------------------------------------------
# satellite: stats fixes (StageStat percentiles, qps active window)
# ---------------------------------------------------------------------------


def test_stagestat_percentiles_in_summary():
    st = StageStat()
    for i in range(1, 101):
        st.add(i * 1e-3)                         # 1ms .. 100ms
    s = st.summary()
    assert s["p50_us"] == pytest.approx(51_000, rel=0.05)
    assert s["p99_us"] == pytest.approx(100_000, rel=0.02)
    assert s["mean_us"] == pytest.approx(50_500, rel=0.01)
    assert s["count"] == 100


def test_retrieval_stats_qps_active_window():
    clock = FakeClock()
    st = RetrievalStats(clock=clock)
    assert st.qps() == 0.0                       # no traffic: defined
    # burst one: 8 queries over 0.1s
    st.record_submit(8)
    clock.t = 0.1
    st.record_batch(8)
    # a long idle gap must NOT deflate the rate (old bug: the window
    # was first-to-last wall time, so 100s idle -> qps ~ 0.16)
    clock.t = 100.0
    st.record_submit(8)                          # gap clipped to 1.0s
    clock.t = 100.1
    st.record_batch(8)
    assert st.qps() == pytest.approx(16 / 1.2)   # 0.1 + 1.0 + 0.1 active


def test_retrieval_stats_qps_single_instant():
    clock = FakeClock(10.0)
    st = RetrievalStats(clock=clock)
    st.record_submit(5)                          # one instant only
    clock.t = 10.25
    assert st.qps() == pytest.approx(20.0)       # measured to "now"
    clock.t = 500.0                              # ...but idle-clipped:
    assert st.qps() == pytest.approx(5.0)        # never decays below 1s


# ---------------------------------------------------------------------------
# trace-id propagation through a real scheduler wave
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_ralm():
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, size=(64,))
    corpus = [start]
    for _ in range(31):
        corpus.append((3 * corpus[-1] + 1) % 64)
    corpus = np.stack(corpus, axis=1).astype(np.int32)
    ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                          list_cap=512).from_corpus(params, cfg, corpus)
    ccfg = ds.search_config(nprobe=4, k=8, backend="ref")
    rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999,
                    temperature=1.0)
    return cfg, params, corpus, ds, ccfg, rag


def _traced_engine(tiny_ralm, enabled=True, **kw):
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    kw.setdefault("max_seq", 64)
    kw.setdefault("kv_slots", 8)
    kw.setdefault("attn_seq_block", 64)
    eng = RalmEngine.monolithic(params, cfg, rag,
                                ds.async_retriever(ccfg), **kw)
    eng.set_tracer(Tracer(enabled=enabled))
    return eng


def test_trace_id_propagates_through_wave(tiny_ralm):
    """One request, end to end: every span the taxonomy in
    docs/observability.md promises shows up, on the right track, and
    the flow arrow links admission to the first-token wave."""
    corpus = tiny_ralm[2]
    eng = _traced_engine(tiny_ralm)
    req = RalmRequest(prompt=jnp.asarray(corpus[:2, :8]), steps=3,
                      tenant="traced")
    rid = eng.submit(req)
    assert req.trace_id == rid                   # defaulted at submit
    eng.run()
    # a program new to this process: JAX compiles it whatever ran before
    jax.jit(lambda x: x * 5 - 1)(jnp.arange(3)).block_until_ready()

    doc = eng.tracer.export()
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    for expected in ("queue.wait", "sched.admit", "prefill",
                     "prefill.scatter", "sched.step",
                     "wave.decode", "wave.search", "wave.finish",
                     "wave.mix", "wave.sample", "wave.stream",
                     "request.finish",
                     "retrieval.queue_wait", "retrieval.scan",
                     "retrieval.merge", "retrieval.gather",
                     "kvpool.alloc", "kvpool.release",
                     "jit.compile"):
        assert expected in names, f"span {expected!r} missing"
    compiles = [e for e in evs if e["name"] == "jit.compile"]
    assert all(isinstance(e["args"]["fun_name"], str)
               and e["args"]["seconds"] >= 0 for e in compiles)
    tracks = {e["tid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    by_name = {e["name"]: e for e in evs if e["ph"] in ("X", "i")}
    assert tracks[by_name["wave.decode"]["tid"]] == "wave"
    assert tracks[by_name["retrieval.scan"]["tid"]] == "retrieval"
    assert tracks[by_name["kvpool.alloc"]["tid"]] == "kvpool"
    # the request's identity rides the spans...
    admit = by_name["sched.admit"]
    assert admit["args"]["request_id"] == rid
    # the 8-token prompt prefills in the 16-position bucket
    assert by_name["prefill"]["args"]["prompt_len"] == 8
    assert by_name["prefill"]["args"]["bucket"] == 16
    assert by_name["queue.wait"]["args"]["trace_id"] == rid
    # ...and the flow arrow is paired on exactly that id
    flows = [e for e in evs if e.get("cat") == "flow"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert all(e["id"] == rid for e in flows)
    # one wave span per generated token
    steps = [e for e in evs if e["name"] == "sched.step"]
    assert len(steps) == 3
    # TTFT decomposes: queue.wait ends before the first wave ends
    qw = by_name["queue.wait"]
    assert qw["ts"] + qw["dur"] <= steps[0]["ts"] + steps[0]["dur"] + 1.0


def test_disabled_tracer_records_nothing_on_wave(tiny_ralm):
    """Same workload, tracing off: zero events, and outputs are
    byte-identical to the traced engine (observability is read-only)."""
    corpus = tiny_ralm[2]
    on = _traced_engine(tiny_ralm)
    off = _traced_engine(tiny_ralm, enabled=False)
    out_on = np.asarray(on.generate(jnp.asarray(corpus[:2, :8]), steps=3))
    out_off = np.asarray(off.generate(jnp.asarray(corpus[:2, :8]), steps=3))
    assert off.tracer.events() == []
    assert len(on.tracer.events()) > 0
    np.testing.assert_array_equal(out_on, out_off)


def test_gather_span_in_the_serving_configuration(tiny_ralm):
    """``measure=False`` (how the engine serves): the payload gather is
    still a span, around its dispatch, and no stage time is recorded,
    since that would need a host sync."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    retriever = ds.async_retriever(ccfg,
                                   service_cfg=ServiceConfig(measure=False))
    eng = RalmEngine.monolithic(params, cfg, rag, retriever, max_seq=64,
                                kv_slots=8, attn_seq_block=64)
    eng.set_tracer(Tracer(enabled=True))
    eng.generate(jnp.asarray(corpus[:2, :8]), steps=2)
    names = {e["name"] for e in eng.tracer.events()}
    assert "retrieval.gather" in names
    assert retriever.service.stats.gather.count == 0


# ---------------------------------------------------------------------------
# the profiler sink: spans in jax.profiler's trace, on the device's clock
# ---------------------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body()`` inside a profiler session; returns the host plane's
    events as {name: [(start ns, end ns), ...]}. The session is stopped
    whatever happens, so no later test sees an active profiler."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(inner, outers):
    a, b = inner
    return any(lo <= a and b <= hi for lo, hi in outers)


def test_profiler_sink_nests_on_one_clock(tmp_path):
    """Inside a profiler session every tracer's spans land in the trace as
    ``ralm.<name>``, args as metadata (the name is left as it is),
    nested as opened and on the clock of an enclosing annotation; an
    enabled tracer fills its Chrome buffer as well. Outside a session
    the disabled tracer is null again."""
    chrome = Tracer(enabled=True)
    assert not NULL_TRACER.active
    with NULL_TRACER.span("before") as sp:
        assert sp is NULL_SPAN

    def body():
        assert NULL_TRACER.active
        with jax.profiler.TraceAnnotation("outer"):
            with NULL_TRACER.span("a.outer", "wave", args={"rows": 3}):
                with chrome.span("a.inner", "wave"):
                    jnp.ones(4).block_until_ready()
    ev = _profiled(tmp_path, body)
    assert NULL_TRACER.span("after") is NULL_SPAN
    assert "ralm.before" not in ev and "ralm.after" not in ev
    (outer,), (a,), (b,) = ev["outer"], ev["ralm.a.outer"], ev["ralm.a.inner"]
    assert _inside(a, [outer]) and _inside(b, [a])
    assert [e["name"] for e in chrome.events()
            if e["name"].startswith("a.")] == ["a.inner"]
    assert NULL_TRACER.events() == []


def test_profiled_wave_names_its_phases(tiny_ralm, tmp_path):
    """A wave of the tiny model under a profiler session, tracer off:
    prefill, the wave's phases and the completion work are named in the
    trace, each nested where it runs, and no name is the harness's."""
    corpus = tiny_ralm[2]
    eng = _traced_engine(tiny_ralm, enabled=False)
    prompt = jnp.asarray(corpus[:2, :8])
    eng.generate(prompt, steps=3)          # compile outside the session
    ev = _profiled(tmp_path, lambda: eng.generate(prompt, steps=3))
    assert eng.tracer.events() == []
    steps, admits = ev["ralm.sched.step"], ev["ralm.sched.admit"]
    assert len(steps) == 3
    for name in ("ralm.wave.decode", "ralm.wave.search", "ralm.wave.finish",
                 "ralm.retrieval.scan"):
        assert all(_inside(s, steps) for s in ev[name]), name
    for name in ("ralm.wave.mix", "ralm.wave.sample", "ralm.wave.stream"):
        assert len(ev[name]) == 3
        assert all(_inside(s, ev["ralm.wave.finish"]) for s in ev[name])
    assert all(_inside(s, ev["ralm.wave.mix"])
               for s in ev["ralm.retrieval.gather"])
    for name in ("ralm.prefill", "ralm.prefill.scatter"):
        assert len(ev[name]) == 1 and _inside(ev[name][0], admits)
    assert len(ev["ralm.request.finish"]) == 1
    assert not any(n.startswith("bench.") for n in ev)


def test_compile_listener_is_registered_once():
    """Every enabled tracer gets each of JAX's compiles once, as a
    ``jit.compile`` span with the function's name and its seconds,
    however many tracers listen."""
    a, b = Tracer(enabled=True), Tracer(enabled=True)

    def times_seven(x):
        return x * 7 + 2
    jax.jit(times_seven)(jnp.arange(4)).block_until_ready()
    for tr in (a, b):
        evs = [e for e in tr.events() if e["name"] == "jit.compile"
               and "times_seven" in e["args"]["fun_name"]]
        assert len(evs) == 1 and evs[0]["ph"] == "X"
        assert evs[0]["args"]["seconds"] >= 0


# ---------------------------------------------------------------------------
# the gateway endpoints: /metricsz, /tracez, /statsz satellites
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obs_gw(tiny_ralm):
    # the Gateway snapshots engine.tracer at construction: install first
    eng = _traced_engine(tiny_ralm)
    gateway = Gateway(eng, GatewayConfig())
    gateway.start_background()
    # one real completion so the latency histograms have data
    _stream_one(gateway.port, tiny_ralm[2][0, :8].tolist())
    yield gateway
    gateway.shutdown()


def _get(port, path):
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    s.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    raw = b""
    while True:
        data = s.recv(65536)
        if not data:
            break
        raw += data
    s.close()
    head, body = raw.split(b"\r\n\r\n", 1)
    status = int(head.split(b"\r\n")[0].split()[1])
    headers = {}
    for ln in head.decode().split("\r\n")[1:]:
        k, v = ln.split(":", 1)
        headers[k.strip().lower()] = v.strip()
    return status, headers, body


def _stream_one(port, prompt, max_tokens=4):
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True}).encode()
    req = (f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
           f"Content-Length: {len(body)}\r\n\r\n").encode() + body
    s = socket.create_connection(("127.0.0.1", port), timeout=120)
    s.sendall(req)
    buf = b""
    while b"data: [DONE]\n\n" not in buf:
        data = s.recv(4096)
        assert data, "stream closed early"
        buf += data
    s.close()


def test_gateway_metricsz_exposition(obs_gw):
    status, headers, body = _get(obs_gw.port, "/metricsz")
    assert status == 200
    assert headers["content-type"].startswith("text/plain")
    text = body.decode()
    typed, seen = _check_exposition(text)
    # the client-facing SLO families have real observations
    samples = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, v = line.rpartition(" ")
            samples[name] = float(v)
    assert samples["ralm_ttft_seconds_count"] >= 1
    assert samples["ralm_ttft_seconds_p50"] > 0
    assert samples["ralm_completions_total"] >= 1
    assert samples["ralm_tokens_out_total"] >= 1
    assert samples['ralm_admission_total{outcome="admitted"}'] >= 1
    assert samples['ralm_kv_slots{state="used"}'] == 0   # idle now
    assert "ralm_retrieval_queries_total" in samples
    # one 8-token prompt so far, padded to its 16-position bucket
    assert samples["ralm_prefill_calls_total"] >= 1
    assert samples["ralm_prefill_programs"] >= 1
    assert samples['ralm_prefill_tokens_total{kind="prompt"}'] >= 8
    assert samples['ralm_prefill_tokens_total{kind="pad"}'] >= 8
    assert samples['ralm_retrieval_stage_seconds'
                   '{stage="scan",stat="p99"}'] >= 0


def test_gateway_tracez_roundtrip_and_clear(obs_gw):
    status, _, body = _get(obs_gw.port, "/tracez")
    assert status == 200
    doc = json.loads(body)
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"sched.step", "wave.decode", "retrieval.scan"} <= names
    # drain the ring: the next scrape holds only track metadata
    status, _, body = _get(obs_gw.port, "/tracez?clear=1")
    assert status == 200
    assert validate_chrome_trace(json.loads(body)) == []
    _, _, body = _get(obs_gw.port, "/tracez")
    leftover = json.loads(body)["traceEvents"]
    assert all(e["ph"] == "M" for e in leftover)
    # and the tracer keeps recording after a clear
    _stream_one(obs_gw.port, [1, 2, 3, 4], max_tokens=2)
    _, _, body = _get(obs_gw.port, "/tracez")
    doc = json.loads(body)
    assert validate_chrome_trace(doc) == []
    assert any(e["name"] == "sched.step" for e in doc["traceEvents"])


def test_gateway_statsz_satellite_fields(obs_gw):
    _, _, body = _get(obs_gw.port, "/statsz")
    stats = json.loads(body)
    kv = stats["kv_pool"]
    for key in ("decode_compiles", "skip_fraction", "blocks_total",
                "blocks_skipped"):
        assert key in kv, key
    assert kv["decode_compiles"] >= 1
    pf = stats["prefill"]
    assert pf["calls"] >= 1 and pf["programs"] >= 1
    assert pf["prompt_tokens"] >= 8 and pf["pad_tokens"] >= 8
    kern = stats["kernels"]
    assert isinstance(kern["fallbacks"], dict)
    assert kern["fallback_total"] == sum(kern["fallbacks"].values())
    ret = stats["retrieval"]
    assert "p50_us" in ret["scan"] and "p99_us" in ret["scan"]
    assert ret["qps"] >= 0
    # /statsz is an aggregated view of the SAME registry as /metricsz
    assert stats["metrics"]["ralm_completions_total"] >= 1
    assert stats["metrics"]["ralm_ttft_seconds"]["count"] >= 1
