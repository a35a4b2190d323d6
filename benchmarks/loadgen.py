"""Traffic-scale load harness for the serving front door.

Drives the ``repro.serve.gateway`` HTTP endpoint with real sockets —
the full network path: admission, SSE streaming, backpressure — under
two disciplines:

  * **closed loop** — ``concurrency`` workers in lockstep back-to-back
    request loops. No queueing delay by construction, so the achieved
    request rate *is* the deployment's capacity; it calibrates the
    open-loop sweep.
  * **open loop** — Poisson arrivals at ``rate`` rps (exponential
    inter-arrival gaps), heavy-tailed prompt/output lengths (lognormal,
    clipped; prompt lengths quantized to a few buckets so prefill
    compiles amortize the way a real tokenizer's padding buckets
    would), multi-tenant mix. Open-loop arrivals do not slow down when
    the server does — the honest way to measure tail latency under
    load (closed-loop clients self-throttle and hide the queue).

Per request the client records client-side TTFT (first SSE data chunk
after send) and TPOT (mean gap over streamed tokens), plus the
server-reported degrade levels from the final chunk's ``ralm``
extension. ``main()`` sweeps offered load at fractions of measured
capacity — including >= 2x overload — and merges a ``traffic`` section
into ``BENCH_serve.json``:

  * p50/p99 TTFT and TPOT per load level, achieved tokens/s,
  * shed counts (429 quota / 503 backpressure) and degrade-ladder
    transitions (the overload level must engage the ladder; the
    unloaded level must stay at baseline),
  * a greedy-parity replay: requests served entirely inside ONE
    degrade level are re-run in-process with that level's (nprobe,
    interval, mode) pinned — streamed bytes must equal engine bytes,
    under load and under degradation alike.

Stdlib-only client (socket + json + threading): the harness must not
need anything the gateway itself does not.
"""
from __future__ import annotations

import dataclasses
import json
import socket
import statistics
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# one HTTP/SSE request over a raw socket
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RequestRecord:
    """Client-side view of one completion request."""
    tenant: str
    prompt: List[int]
    max_tokens: int
    status: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    send_t: float = 0.0
    first_tok_t: Optional[float] = None
    done_t: Optional[float] = None
    degrade_levels: List[int] = dataclasses.field(default_factory=list)
    error: str = ""

    @property
    def ttft_s(self) -> Optional[float]:
        return (None if self.first_tok_t is None
                else self.first_tok_t - self.send_t)

    @property
    def tpot_s(self) -> Optional[float]:
        if (self.first_tok_t is None or self.done_t is None
                or len(self.tokens) < 2):
            return None
        return (self.done_t - self.first_tok_t) / (len(self.tokens) - 1)


def complete_streaming(host: str, port: int, prompt: List[int],
                       max_tokens: int, tenant: str = "default",
                       timeout: float = 600.0) -> RequestRecord:
    """POST /v1/completions with ``stream: true``; parse the SSE stream
    to the ``[DONE]`` terminator, timestamping the first token."""
    rec = RequestRecord(tenant=tenant, prompt=list(prompt),
                        max_tokens=max_tokens)
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True}).encode()
    req = (f"POST /v1/completions HTTP/1.1\r\nHost: lg\r\n"
           f"X-Tenant: {tenant}\r\nContent-Length: {len(body)}\r\n"
           f"\r\n").encode() + body
    try:
        s = socket.create_connection((host, port), timeout=timeout)
    except OSError as e:
        rec.error = f"connect: {e}"
        return rec
    try:
        rec.send_t = time.perf_counter()
        s.sendall(req)
        buf = b""
        while b"\r\n\r\n" not in buf:
            data = s.recv(65536)
            if not data:
                rec.error = "closed before headers"
                return rec
            buf += data
        head, buf = buf.split(b"\r\n\r\n", 1)
        rec.status = int(head.split(b"\r\n")[0].split()[1])
        if rec.status != 200:
            while s.recv(65536):
                pass
            return rec
        while True:
            # consume complete events as they land: the FIRST token's
            # timestamp must be taken at arrival, not after [DONE]
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                payload = event[6:]
                if payload == b"[DONE]":
                    rec.done_t = rec.done_t or time.perf_counter()
                    return rec
                obj = json.loads(payload)
                choice = obj["choices"][0]
                if choice["finish_reason"] is None:
                    if rec.first_tok_t is None:
                        rec.first_tok_t = time.perf_counter()
                    rec.tokens += [int(t) for t in
                                   choice["text"].split()]
                else:
                    rec.done_t = time.perf_counter()
                    rec.degrade_levels = list(
                        obj.get("ralm", {}).get("degrade_levels", []))
            data = s.recv(65536)
            if not data:
                rec.error = "closed before [DONE]"
                return rec
            buf += data
    except OSError as e:
        rec.error = f"io: {e}"
        return rec
    finally:
        s.close()


def _get(host: str, port: int, path: str, timeout: float = 30.0) -> bytes:
    s = socket.create_connection((host, port), timeout=timeout)
    s.sendall(f"GET {path} HTTP/1.1\r\nHost: lg\r\n\r\n".encode())
    buf = b""
    while True:
        data = s.recv(65536)
        if not data:
            break
        buf += data
    s.close()
    return buf.split(b"\r\n\r\n", 1)[1]


def get_statsz(host: str, port: int, timeout: float = 30.0) -> dict:
    return json.loads(_get(host, port, "/statsz", timeout))


def get_metricsz(host: str, port: int, timeout: float = 30.0) -> str:
    """Prometheus text exposition from the gateway's /metricsz."""
    return _get(host, port, "/metricsz", timeout).decode()


def get_tracez(host: str, port: int, clear: bool = False,
               timeout: float = 30.0) -> dict:
    """Chrome trace-event JSON from /tracez (clear=True drains the
    buffer — the per-load-level capture boundary)."""
    path = "/tracez?clear=1" if clear else "/tracez"
    return json.loads(_get(host, port, path, timeout))


def parse_prometheus(text: str) -> Dict[str, float]:
    """Sample name (incl. label string) -> value, comments skipped."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrafficMix:
    """Heavy-tailed, multi-tenant open-loop traffic shape."""
    tenants: Tuple[str, ...] = ("alpha", "beta", "gamma")
    tenant_weights: Tuple[float, ...] = (0.6, 0.3, 0.1)
    prompt_buckets: Tuple[int, ...] = (4, 8, 16)   # quantized lengths
    prompt_sigma: float = 0.6        # lognormal spread over buckets
    out_mean: int = 8                # lognormal median output length
    out_sigma: float = 0.7
    out_max: int = 32


class _Lcg:
    """Tiny deterministic PRNG (stdlib-only; numpy stays out of the
    client path)."""

    def __init__(self, seed: int):
        self.state = (seed * 2862933555777941757 + 3037000493) % (1 << 64)

    def uniform(self) -> float:
        self.state = (self.state * 6364136223846793005
                      + 1442695040888963407) % (1 << 64)
        return ((self.state >> 11) & ((1 << 53) - 1)) / float(1 << 53)

    def expovariate(self, rate: float) -> float:
        import math
        return -math.log(1.0 - self.uniform()) / rate

    def lognormal(self, median: float, sigma: float) -> float:
        import math
        # Box-Muller from two uniforms
        u1, u2 = max(self.uniform(), 1e-12), self.uniform()
        z = math.sqrt(-2.0 * math.log(u1)) * math.cos(2 * math.pi * u2)
        return median * math.exp(sigma * z)

    def choice_weighted(self, items: Sequence, weights: Sequence[float]):
        x = self.uniform() * sum(weights)
        for item, w in zip(items, weights):
            x -= w
            if x <= 0:
                return item
        return items[-1]


def _sample_request(rng: _Lcg, mix: TrafficMix, corpus_row: List[int],
                    max_total: int) -> Tuple[str, List[int], int]:
    tenant = rng.choice_weighted(mix.tenants, mix.tenant_weights)
    want = rng.lognormal(float(mix.prompt_buckets[1]), mix.prompt_sigma)
    plen = min(mix.prompt_buckets, key=lambda b: abs(b - want))
    out = int(round(rng.lognormal(float(mix.out_mean), mix.out_sigma)))
    out = max(2, min(mix.out_max, out, max_total - plen))
    return tenant, corpus_row[:plen], out


def run_closed_loop(host: str, port: int, corpus: List[List[int]],
                    concurrency: int, duration_s: float,
                    prompt_len: int = 8, max_tokens: int = 8
                    ) -> List[RequestRecord]:
    """``concurrency`` workers, back-to-back requests, fixed shape:
    the achieved rate is the capacity at that concurrency."""
    records: List[RequestRecord] = []
    lock = threading.Lock()
    deadline = time.perf_counter() + duration_s

    def worker(i: int) -> None:
        while time.perf_counter() < deadline:
            prompt = corpus[i % len(corpus)][:prompt_len]
            rec = complete_streaming(host, port, prompt, max_tokens,
                                     tenant=f"closed{i % 2}")
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s * 20 + 600)
    return records


def run_open_loop(host: str, port: int, corpus: List[List[int]],
                  rate_rps: float, duration_s: float, max_total: int,
                  mix: Optional[TrafficMix] = None, seed: int = 0,
                  max_in_flight: int = 64) -> List[RequestRecord]:
    """Poisson arrivals at ``rate_rps`` for ``duration_s``. Arrivals
    are non-blocking (one thread each, bounded by ``max_in_flight`` —
    beyond that the client drops the arrival and records it as shed
    client-side, so a wedged server cannot wedge the harness)."""
    mix = mix or TrafficMix()
    rng = _Lcg(seed)
    records: List[RequestRecord] = []
    lock = threading.Lock()
    threads: List[threading.Thread] = []
    gate = threading.Semaphore(max_in_flight)

    def fire(tenant: str, prompt: List[int], out: int) -> None:
        try:
            rec = complete_streaming(host, port, prompt, out,
                                     tenant=tenant)
        finally:
            gate.release()
        with lock:
            records.append(rec)

    t_end = time.perf_counter() + duration_s
    i = 0
    while True:
        gap = rng.expovariate(rate_rps)
        now = time.perf_counter()
        if now + gap >= t_end:
            break
        time.sleep(gap)
        tenant, prompt, out = _sample_request(
            rng, mix, corpus[i % len(corpus)], max_total)
        i += 1
        if not gate.acquire(blocking=False):
            rec = RequestRecord(tenant=tenant, prompt=prompt,
                                max_tokens=out,
                                error="client in-flight bound")
            with lock:
                records.append(rec)
            continue
        th = threading.Thread(target=fire, args=(tenant, prompt, out),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=600)
    return records


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------


def _pct(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    qs = statistics.quantiles(xs, n=100, method="inclusive")
    return qs[min(98, max(0, int(round(q * 100)) - 1))]


def summarize(records: List[RequestRecord], duration_s: float
              ) -> Dict[str, object]:
    ok = [r for r in records if r.status == 200 and not r.error]
    ttft = sorted(r.ttft_s * 1e3 for r in ok if r.ttft_s is not None)
    tpot = sorted(r.tpot_s * 1e3 for r in ok if r.tpot_s is not None)
    ntok = sum(len(r.tokens) for r in ok)
    return dict(
        offered=len(records),
        completed=len(ok),
        rejected_429=sum(r.status == 429 for r in records),
        rejected_503=sum(r.status == 503 for r in records),
        client_errors=sum(bool(r.error) for r in records),
        tokens_streamed=ntok,
        tokens_per_s=ntok / duration_s,
        achieved_rps=len(ok) / duration_s,
        ttft_ms_p50=_pct(ttft, 0.50), ttft_ms_p99=_pct(ttft, 0.99),
        tpot_ms_p50=_pct(tpot, 0.50), tpot_ms_p99=_pct(tpot, 0.99),
        degraded_requests=sum(
            1 for r in ok if any(lv != 0 for lv in r.degrade_levels)),
        tenants=sorted({r.tenant for r in records}),
    )


# ---------------------------------------------------------------------------
# the bench: capacity -> load sweep -> parity replay
# ---------------------------------------------------------------------------

MAX_SEQ = 64
KV_SLOTS = 8


def _build_gateway():
    import dataclasses as dc

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import transformer as tf
    from repro.serve import DatastoreBuilder, RagConfig, RalmEngine
    from repro.serve.gateway import (DegradeConfig, Gateway,
                                     GatewayConfig)

    cfg = dc.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, size=(64,))
    seqs = [start]
    for _ in range(31):
        seqs.append((3 * seqs[-1] + 1) % 64)
    corpus = np.stack(seqs, axis=1).astype(np.int32)
    ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                          list_cap=512).from_corpus(params, cfg, corpus)
    ccfg = ds.search_config(nprobe=4, k=8)
    rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999,
                    temperature=1.0)

    def make_engine(nprobe=4, interval=1, mode="knnlm"):
        c = dc.replace(ccfg, nprobe=nprobe)
        r = dc.replace(rag, interval=interval, mode=mode)
        return RalmEngine.monolithic(params, cfg, r, ds.retriever(c),
                                     max_seq=MAX_SEQ, kv_slots=KV_SLOTS,
                                     attn_seq_block=MAX_SEQ)

    # the Gateway snapshots engine.tracer at construction, so install
    # the live tracer on the serving engine BEFORE building it
    from repro.obs import Tracer
    eng = make_engine()
    eng.set_tracer(Tracer(enabled=True, capacity=1 << 17))
    gw = Gateway(eng, GatewayConfig(
        max_queue_depth=12,
        degrade=DegradeConfig(high_watermark=4, low_watermark=1,
                              patience=2, recovery=200)))
    return gw, corpus.tolist(), make_engine


def _parity_replay(records: List[RequestRecord], ladder: List[dict],
                   make_engine) -> List[Dict[str, object]]:
    """Greedy parity under load: replay requests served entirely at one
    degrade level with that level's settings pinned in-process."""
    import jax.numpy as jnp
    import numpy as np

    by_level: Dict[int, RequestRecord] = {}
    for rec in records:
        if (rec.status == 200 and not rec.error and rec.tokens
                and len(rec.degrade_levels) == 1):
            by_level.setdefault(rec.degrade_levels[0], rec)
    out = []
    for level, rec in sorted(by_level.items()):
        spec = ladder[level]
        eng = make_engine(nprobe=max(1, spec["nprobe"]),
                          interval=spec["interval"],
                          mode="knnlm" if spec["knn"] else "none")
        ref = np.asarray(eng.generate(jnp.asarray([rec.prompt]),
                                      steps=len(rec.tokens)))
        ref = ref[0, len(rec.prompt):].tolist()
        out.append(dict(level=level, level_name=spec["name"],
                        tokens=len(rec.tokens),
                        match=ref == rec.tokens))
    return out


def main(out_path: str = "BENCH_serve.json",
         capacity_s: float = 12.0, level_s: float = 12.0,
         load_fractions: Sequence[float] = (0.25, 0.5, 1.0, 2.0, 3.0)
         ) -> None:
    gw, corpus, make_engine = _build_gateway()
    base = gw.start_background()
    host, port = "127.0.0.1", gw.port
    print(f"gateway up at {base}")

    # warm every compile bucket the mix can hit (prompt-length prefills
    # x wave-size decode graphs) so the sweep measures serving, not XLA
    mix = TrafficMix()
    client_pool: List[RequestRecord] = []   # every request the server saw
    t0 = time.perf_counter()
    for plen in mix.prompt_buckets:
        client_pool.append(
            complete_streaming(host, port, corpus[0][:plen], 4))
    client_pool.extend(
        run_closed_loop(host, port, corpus, concurrency=KV_SLOTS,
                        duration_s=2.0))
    print(f"warmup {time.perf_counter() - t0:.1f}s")

    # closed loop: the capacity calibration
    t0 = time.perf_counter()
    closed = run_closed_loop(host, port, corpus,
                             concurrency=KV_SLOTS,
                             duration_s=capacity_s)
    closed_sum = summarize(closed, time.perf_counter() - t0)
    capacity_rps = max(closed_sum["achieved_rps"], 0.5)
    print(f"closed-loop capacity: {capacity_rps:.2f} rps, "
          f"{closed_sum['tokens_per_s']:.1f} tok/s")

    import os

    from repro.obs import validate_chrome_trace
    os.makedirs("traces", exist_ok=True)

    levels = []
    parity_pool: List[RequestRecord] = []
    client_pool.extend(closed)
    for frac in load_fractions:
        pre = get_statsz(host, port)
        get_tracez(host, port, clear=True)   # level capture boundary
        rate = capacity_rps * frac
        t0 = time.perf_counter()
        recs = run_open_loop(host, port, corpus, rate_rps=rate,
                             duration_s=level_s, max_total=MAX_SEQ,
                             mix=mix, seed=int(frac * 1000))
        row = summarize(recs, time.perf_counter() - t0)
        post = get_statsz(host, port)
        trace_doc = get_tracez(host, port)
        problems = validate_chrome_trace(trace_doc)
        trace_path = os.path.join("traces",
                                  f"loadgen_x{frac}.trace.json")
        with open(trace_path, "w") as f:
            json.dump(trace_doc, f)
        row.update(trace_path=trace_path,
                   trace_events=len(trace_doc["traceEvents"]),
                   trace_valid=not problems)
        row.update(
            load_fraction=frac, offered_rps=rate,
            degrade_level_end=post["degrade"]["level"],
            degrade_transitions_down=(
                post["degrade"]["transitions_down"]
                - pre["degrade"]["transitions_down"]),
            degrade_transitions_up=(post["degrade"]["transitions_up"]
                                    - pre["degrade"]["transitions_up"]),
            server_rejected_quota=(post["admission"]["rejected_quota"]
                                   - pre["admission"]["rejected_quota"]),
            server_rejected_capacity=(
                post["admission"]["rejected_capacity"]
                - pre["admission"]["rejected_capacity"]))
        levels.append(row)
        parity_pool.extend(recs)
        client_pool.extend(recs)
        print(f"open loop x{frac}: {row['completed']}/{row['offered']} ok,"
              f" 503={row['rejected_503']},"
              f" ttft p50/p99={row['ttft_ms_p50']:.0f}/"
              f"{row['ttft_ms_p99']:.0f}ms,"
              f" down={row['degrade_transitions_down']},"
              f" trace={row['trace_events']}ev"
              f" valid={row['trace_valid']}")
        # let the backlog drain + ladder recover between levels
        while get_statsz(host, port)["scheduler"]["active_requests"]:
            time.sleep(0.25)

    ladder = get_statsz(host, port)["degrade"]["ladder"]
    final_stats = get_statsz(host, port)
    prom = parse_prometheus(get_metricsz(host, port))
    gw.shutdown()

    # server-vs-client consistency: the gateway's TTFT histogram covers
    # the WHOLE run (warmup + closed + every open level), so compare its
    # reservoir percentiles against the pooled client-side distribution
    client_ttft = sorted(r.ttft_s for r in client_pool
                         if r.status == 200 and not r.error
                         and r.ttft_s is not None)

    def _within(client: Optional[float], server: Optional[float],
                tol: float = 0.10) -> Optional[bool]:
        if not client or server is None:
            return None
        return abs(server - client) <= tol * client

    consistency = {}
    for q, key in ((0.50, "p50"), (0.99, "p99")):
        c = _pct(client_ttft, q)
        srv = prom.get(f"ralm_ttft_seconds_{key}")
        consistency[key] = dict(
            client_s=c, server_s=srv, within_10pct=_within(c, srv))
    print("ttft client-vs-server:", consistency)

    parity = _parity_replay(parity_pool, ladder, make_engine)
    print("parity:", parity)

    traffic = dict(
        meta=dict(
            note="loadgen drives the gateway over real HTTP (SSE "
                 "streaming, raw sockets). closed = lockstep capacity "
                 "calibration at concurrency=kv_slots; each open-loop "
                 "level offers Poisson arrivals at load_fraction x "
                 "that capacity with heavy-tailed lognormal "
                 "prompt/output lengths over a 3-tenant mix. TTFT/TPOT "
                 "are CLIENT-side (socket send -> first SSE chunk). "
                 "parity replays single-level requests in-process with "
                 "that degrade level's (nprobe, interval, mode) pinned "
                 "— streamed bytes must match engine bytes. Each level "
                 "also captures a Chrome trace via /tracez (written "
                 "under traces/, open at https://ui.perfetto.dev) and "
                 "the run ends with a client-vs-/metricsz TTFT "
                 "consistency check.",
            max_seq=MAX_SEQ, kv_slots=KV_SLOTS,
            max_queue_depth=12, ladder=ladder),
        closed=dict(concurrency=KV_SLOTS, **closed_sum),
        levels=levels,
        parity=parity,
        metrics_consistency=dict(
            note="client-side TTFT percentiles over EVERY request the "
                 "server saw (warmup + closed + all open levels) vs the "
                 "gateway's /metricsz ralm_ttft_seconds reservoir "
                 "quantiles; acceptance is within_10pct.",
            ttft=consistency),
        server=dict(
            completions=final_stats["completions"],
            cancelled=final_stats["cancelled"],
            disconnects=final_stats["disconnects"],
            tokens_out=final_stats["tokens_out"],
            degrade=final_stats["degrade"],
            admission=final_stats["admission"],
            metricsz=dict(sorted(
                (k, v) for k, v in prom.items()
                if "_bucket" not in k))),
    )

    try:
        with open(out_path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        doc = {}
    doc["traffic"] = traffic
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)

    overload = [r for r in levels if r["load_fraction"] >= 2.0]
    engaged = any(r["degrade_transitions_down"] > 0 or
                  r["rejected_503"] > 0 for r in overload)
    bounded = all(r["client_errors"] == 0 for r in levels)
    parity_ok = parity and all(p["match"] for p in parity)
    print(f"wrote {out_path} (traffic section, {len(levels)} levels); "
          f"overload sheds or degrades: {engaged}; "
          f"all responses bounded: {bounded}; "
          f"greedy parity incl. degraded levels: {parity_ok}")


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
