"""Observability layer: concurrent metric correctness, histogram bucket
properties, exposition well-formedness, and cross-process trace
propagation over a real spawned node."""

import threading
import urllib.request

import pytest

from repro.obs import (MetricsRegistry, TraceContext, activate,
                       current_trace, maybe_span, render_prometheus)
from repro.obs.metrics import Histogram
from repro.obs.tracing import TRACE_ID_BYTES


# ------------------------------------------------------------- concurrency
def test_concurrent_counters_exact():
    """8 threads hammer one counter, one gauge, and one histogram; totals
    must be exact — a lost update is a data race in the striped locks."""
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2_000
    c = reg.counter("repro_test_hits_total")
    g = reg.gauge("repro_test_depth")
    h = reg.histogram("repro_test_latency_seconds")
    barrier = threading.Barrier(n_threads)

    def hammer(tid: int):
        barrier.wait()
        for i in range(n_iter):
            c.inc()
            g.inc(2.0)
            g.dec(1.0)
            h.observe(1e-5 * (1 + (i + tid) % 7))

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    total = n_threads * n_iter
    assert c.value == total
    assert g.value == total  # +2 -1 per iteration
    snap = h.snapshot()
    assert snap["count"] == total
    assert snap["buckets"][-1][1] == total  # +Inf bucket is cumulative total


def test_concurrent_get_or_create_same_instrument():
    """Racing get-or-create must converge on one instrument per name."""
    reg = MetricsRegistry()
    got = []
    barrier = threading.Barrier(8)

    def create():
        barrier.wait()
        got.append(reg.counter("repro_test_races_total"))

    threads = [threading.Thread(target=create) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(c is got[0] for c in got)
    got[0].inc()
    assert reg.snapshot()["counters"]["repro_test_races_total"] == 1.0


# -------------------------------------------------------------- histograms
def test_histogram_bucket_boundaries():
    """le semantics: a value exactly on a bound lands in that bound's
    bucket; one ulp above goes to the next; above the top bound -> +Inf."""
    h = Histogram("repro_test_h", start=1e-3, factor=2.0, buckets=4)
    bounds = h.bounds
    assert bounds == (1e-3, 2e-3, 4e-3, 8e-3)
    assert h.bucket_index(1e-3) == 0  # v <= le inclusive
    assert h.bucket_index(1e-3 * 1.0000001) == 1
    assert h.bucket_index(2e-3) == 1
    assert h.bucket_index(5e-3) == 3
    assert h.bucket_index(8e-3) == 3
    assert h.bucket_index(9e-3) == 4  # +Inf slot
    assert h.bucket_index(0.0) == 0


def test_histogram_quantiles_bounded_by_observations():
    h = Histogram("repro_test_h2")
    for v in (0.001, 0.002, 0.004, 0.100):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.100)
    # interpolated quantiles stay inside the observed range and are ordered
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # cumulative buckets are monotone and end at the total
    cums = [c for _, c in s["buckets"]]
    assert cums == sorted(cums) and cums[-1] == 4


def test_registry_type_clash_raises():
    reg = MetricsRegistry()
    reg.counter("repro_test_name")
    with pytest.raises(ValueError):
        reg.gauge("repro_test_name")
    with pytest.raises(ValueError):
        reg.histogram("repro_test_name")


# -------------------------------------------------------------- exposition
def test_zero_metrics_scrape_well_formed():
    """A scrape before any traffic must still be valid exposition: every
    registered instrument appears with zero values, no crash on empty
    histograms."""
    reg = MetricsRegistry()
    reg.counter("repro_test_zero_total")
    reg.gauge("repro_test_zero_depth")
    reg.histogram("repro_test_zero_seconds")
    text = reg.render_prometheus()
    assert "# TYPE repro_test_zero_total counter" in text
    assert "repro_test_zero_total 0" in text
    assert "repro_test_zero_depth 0" in text
    assert '_bucket{le="+Inf"} 0' in text
    assert "repro_test_zero_seconds_count 0" in text
    assert text.endswith("\n")
    # every non-comment line is "name{labels} value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        parts = line.rsplit(" ", 1)
        assert len(parts) == 2 and parts[0]
        float(parts[1].replace("+Inf", "inf"))


def test_render_prometheus_formats_values():
    snap = {"counters": {"c_total": 3.0}, "gauges": {"g": 1.5},
            "histograms": {}}
    text = render_prometheus(snap)
    assert "c_total 3\n" in text  # integral floats render as ints
    assert "g 1.5" in text


def test_broken_collector_never_breaks_scrape():
    reg = MetricsRegistry()
    reg.counter("repro_test_ok_total").inc()

    def broken():
        raise RuntimeError("collector bug")

    reg.register_collector(broken)
    snap = reg.snapshot()
    assert snap["counters"]["repro_test_ok_total"] == 1.0


# ----------------------------------------------------------------- tracing
def test_trace_context_spans_and_ids():
    tr = TraceContext()
    assert len(tr.id_bytes()) == TRACE_ID_BYTES
    assert current_trace() is None
    with activate(tr):
        assert current_trace() is tr
        with maybe_span("work"):
            pass
        with maybe_span("work"):
            pass
    assert current_trace() is None
    totals = tr.span_totals()
    assert set(totals) == {"work"} and totals["work"] >= 0.0
    assert len(tr.spans) == 2
    # maybe_span with no active trace is a no-op, not an error
    with maybe_span("orphan"):
        pass
    assert len(tr.spans) == 2


def test_trace_propagates_across_executor():
    """IOExecutor workers must inherit the submitter's trace — the engine
    relies on this for prefetch spans."""
    from repro.runtime.executor import IOExecutor

    tr = TraceContext()
    with IOExecutor(max_workers=2) as ex:
        with activate(tr):
            fut = ex.submit(lambda: current_trace())
        assert fut.result(timeout=10) is tr
        # no active trace at submit time -> worker sees none
        fut2 = ex.submit(lambda: current_trace())
        assert fut2.result(timeout=10) is None


# ------------------------------------------------- cross-process (real node)
@pytest.fixture(scope="module")
def local_node(tmp_path_factory):
    from cluster_harness import spawn_nodes

    # generous ready deadline (cluster_harness default): under a
    # full-suite run on a loaded shared container the child interpreter
    # can take >30s just to import jax
    (node,) = spawn_nodes(tmp_path_factory.mktemp("obsnode"), 1,
                          block_size=16, backend="lsm", metrics_port=0)
    yield node
    node.close()


def test_trace_id_propagates_to_node_scrape(local_node):
    """A trace activated around client RPCs must cross the wire: the
    node's OP_METRICS report carries the trace id and a server-side span
    observation."""
    import numpy as np

    from repro.cluster import ClusterKVBlockStore

    store = ClusterKVBlockStore([local_node.address], block_size=16)
    try:
        tr = TraceContext()
        tokens = list(range(32))
        blocks = [np.ones((16, 8), dtype=np.float32)] * 2
        with activate(tr):
            store.put_batch(tokens, blocks, start_block=0)
            store.flush()
            got = store.get_batch(tokens, 32)
        assert len(got) == 2
        m = store.nodes[0].metrics()
        assert tr.trace_id in m["traces"]
        span = m["metrics"]["histograms"]["repro_node_trace_server_span_seconds"]
        assert span["count"] >= 3  # put + flush + get all carried the trace
        assert m["metrics"]["counters"]["repro_node_trace_requests_total"] >= 3
        # untraced RPCs don't count as traced
        untraced_before = m["metrics"]["counters"]["repro_node_trace_requests_total"]
        store.probe(tokens)
        m2 = store.nodes[0].metrics()
        assert m2["metrics"]["counters"]["repro_node_trace_requests_total"] == untraced_before
    finally:
        store.close()


def test_node_http_exposition(local_node):
    """--metrics-port serves Prometheus text over HTTP with per-op
    latency histograms present."""
    assert local_node.metrics_port
    url = f"http://127.0.0.1:{local_node.metrics_port}/metrics"
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert "# TYPE repro_node_request_seconds histogram" in text
    assert "repro_server_requests" in text
    assert "repro_node_request_seconds_p99" in text


def test_scrape_cluster_reports_dead_node_unreachable(tmp_path):
    """scrape_cluster must flag a killed node as unreachable and keep
    returning live nodes' metrics — never hang on the corpse."""
    from cluster_harness import kill_node, spawn_nodes

    from repro.cluster import ClusterKVBlockStore

    nodes = spawn_nodes(tmp_path, 2, block_size=16, backend="lsm")
    store = ClusterKVBlockStore([n.address for n in nodes], block_size=16,
                                retries=0, timeout_s=10.0)
    try:
        kill_node(nodes[1])
        scrape = store.scrape_cluster()
        assert scrape["nodes"][1].get("unreachable")
        assert not scrape["nodes"][0].get("unreachable")
        assert scrape["nodes"][0]["metrics"]["gauges"]["repro_server_requests"] >= 0
        assert 1 in scrape["down"] and 0 in scrape["live"]
        # second scrape: the dead node is already marked down, no RPC retry
        scrape2 = store.scrape_cluster()
        assert scrape2["nodes"][1].get("unreachable")
    finally:
        store.close()
        for n in nodes:
            n.close()


# ------------------------------------------ the engine's always-on timeline
def test_obs_imports_without_jax():
    """Cache-node processes import repro.obs: it must not load JAX, and
    maybe_span must work there with no profiler to write to."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys, repro.obs as o\n"
            "t = o.TraceContext()\n"
            "with o.activate(t):\n"
            "    with o.maybe_span('probe'):\n"
            "        pass\n"
            "assert set(t.span_totals()) == {'probe'}\n"
            "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], env=dict(os.environ, PYTHONPATH=src),
                          timeout=60)
    assert proc.returncode == 0


def test_trace_on_wire_flag_gates_the_wire_id():
    from repro.obs.tracing import wire_trace_id

    assert wire_trace_id() is None
    tr = TraceContext()
    with activate(tr):
        assert wire_trace_id() == tr.id_bytes()
    with activate(TraceContext(on_wire=False)):
        assert wire_trace_id() is None


def _staged_engine(store, block_size: int, tracing: bool, runtime=None):
    from repro.cache.hierarchy import CacheHierarchy
    from repro.configs import get_config
    from repro.serving import ComputeModel, ServingEngine

    h = CacheHierarchy(block_size, 4, 8, store=store)
    return ServingEngine(h, ComputeModel(get_config("glm4-9b")), kv_bytes_per_token=64,
                         max_batch_tokens=256, runtime=runtime, tracing=tracing)


def _serve_twice(eng, prompts):
    """Serve the prompts, settle the writes, then serve them again: the
    second round's hits come from the store (the tiers hold 12 blocks)."""
    for _ in range(2):
        for p in prompts:
            eng.submit(type("R", (), {"tokens": p, "rid": -1, "stage": -1})())
        recs = eng.run()
        eng.drain()
    return recs


def test_untraced_engine_records_store_decode_on_an_io_worker(tmp_path, monkeypatch):
    """With tracing off, each record still carries its spans, the store's
    decode among them, taken on an I/O worker under the request's trace;
    no span histogram is observed."""
    import numpy as np

    from repro.core.store import KVBlockStore
    from repro.runtime import RuntimeServices

    threads = []
    orig = KVBlockStore.get_batch

    def get_batch(self, tokens, n_tokens):
        threads.append(threading.current_thread())
        return orig(self, tokens, n_tokens)

    monkeypatch.setattr(KVBlockStore, "get_batch", get_batch)
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(0, 50_000, 64)] for _ in range(6)]
    store = KVBlockStore(str(tmp_path / "lsm"), block_size=16)
    with RuntimeServices(io_threads=2) as runtime:
        eng = _staged_engine(store, 16, tracing=False, runtime=runtime)
        recs = _serve_twice(eng, prompts)
        snap = eng.metrics_snapshot()
    store.close()
    decoded = [r for r in recs if "decode" in r.spans]
    assert decoded and threads
    assert threading.main_thread() not in threads
    for r in decoded:
        assert {"read", "fetch", "fetch_wait"} <= set(r.spans)
        assert r.spans["decode"] > 0
    assert not [k for k in snap["histograms"] if k.startswith("repro_engine_span_seconds_")]


@pytest.mark.parametrize("tracing", [False, True])
def test_engine_sends_trace_ids_to_nodes_only_when_tracing(tracing):
    import numpy as np
    from cluster_harness import B, close_all, mem_cluster

    servers, cluster = mem_cluster(1, 1)
    try:
        rng = np.random.default_rng(4)
        prompts = [[int(x) for x in rng.integers(0, 50_000, 8 * B)] for _ in range(4)]
        eng = _staged_engine(cluster, B, tracing=tracing)
        recs = _serve_twice(eng, prompts)
        assert any(r.reused_tokens for r in recs)
        traced = servers[0].registry.snapshot()["counters"].get(
            "repro_node_trace_requests_total", 0)
        spans = [k for k in eng.metrics_snapshot()["histograms"]
                 if k.startswith("repro_engine_span_seconds_")]
        if tracing:
            assert traced > 0 and spans
        else:
            assert traced == 0 and not spans
    finally:
        close_all(cluster, servers)
