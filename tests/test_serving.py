"""Serving engine + workload: staged hit rates realized, TTFT accounting,
hedged reads, LSM-vs-baseline ordering on a miniature workload."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.cache.hierarchy import CacheHierarchy
from repro.configs import get_config
from repro.core.baselines import FilePerObjectStore, MemoryOnlyStore
from repro.core.codec import CODEC_INT8, CODEC_RAW, BatchCodec, int8_error_bound
from repro.core.store import KVBlockStore
from repro.runtime import RuntimeServices
from repro.serving import ComputeModel, ServingEngine
from repro.serving.real_model import CompileCounter, RealModel, serve_staged
from repro.workload import PAPER_STAGES, StagedWorkload


def make_engine(tmp_path, backend: str, device_blocks=32, host_blocks=64, budget=None):
    cfg = get_config("glm4-9b")
    if backend == "lsm":
        store = KVBlockStore(str(tmp_path / "lsm"), block_size=16, budget_bytes=budget)
    elif backend == "file":
        store = FilePerObjectStore(str(tmp_path / "file"), block_size=16, budget_bytes=budget)
    else:
        store = None
    h = CacheHierarchy(16, device_blocks, host_blocks, store=store)
    eng = ServingEngine(h, ComputeModel(cfg), kv_bytes_per_token=512, max_batch_tokens=4096)
    return eng


def test_workload_stage_hit_expectations():
    wl = StagedWorkload(prompt_len=256, requests_per_stage=20, stages=(0.0, 0.5, 1.0), block_size=16, seed=1)
    reqs = list(wl.requests())
    assert len(reqs) == 60
    for r in reqs:
        assert len(r.tokens) == 256
    # stage 2 requests share their full prefix with a corpus root
    r2 = [r for r in reqs if r.stage == 2][0]
    assert any(r2.tokens == root[:256] for root in wl.corpus)


def test_engine_hit_rate_tracks_expected(tmp_path):
    wl = StagedWorkload(prompt_len=256, requests_per_stage=12, stages=(0.5,), block_size=16,
                        corpus_size=4, seed=2)
    eng = make_engine(tmp_path, "lsm", device_blocks=4096, host_blocks=4096)
    # warm the corpus so shared prefixes can hit
    for p in wl.warmup_prompts(4 * 256):
        eng.submit(type("R", (), {"tokens": p, "rid": -1, "stage": -1})())
    eng.run()
    recs = []
    for r in wl.stage_requests(0):
        eng.submit(r)
    recs = eng.run()
    hits = np.mean([r.reused_tokens / r.prompt_len for r in recs])
    assert hits >= 0.4  # expected 0.5, block-rounding tolerated


def test_ttft_decomposition(tmp_path):
    eng = make_engine(tmp_path, "lsm")
    wl = StagedWorkload(prompt_len=128, requests_per_stage=3, stages=(0.0,), block_size=16, seed=3)
    for r in wl.stage_requests(0):
        eng.submit(r)
    recs = eng.run()
    for r in recs:
        assert r.ttft_s == pytest.approx(r.io_s + r.compute_s)
        assert r.compute_s > 0


def test_lsm_beats_memory_only_under_pressure(tmp_path):
    """With device+host budgets far below the working set, the disk-backed
    hierarchy must retain (and re-hit) more than memory-only — the paper's
    core claim at miniature scale."""
    wl_kwargs = dict(prompt_len=256, requests_per_stage=10, stages=(0.7, 0.7),
                     block_size=16, corpus_size=6, seed=4)
    results = {}
    for backend in ("lsm", "none"):
        eng = make_engine(tmp_path, backend, device_blocks=8, host_blocks=16)
        wl = StagedWorkload(**wl_kwargs)
        for p in wl.warmup_prompts(6 * 256):
            eng.submit(type("R", (), {"tokens": p, "rid": -1, "stage": -1})())
        eng.run()
        recs = []
        for r in wl.requests():
            eng.submit(r)
        recs = eng.run()
        results[backend] = np.mean([r.reused_tokens / r.prompt_len for r in recs])
    assert results["lsm"] > results["none"]


def test_hedged_read_retries_straggler(tmp_path):
    """A promotion slower than hedge_factor x EWMA is re-issued and the
    faster attempt wins (straggler mitigation)."""
    import time as _time

    from repro.cache.hierarchy import Acquisition

    eng = make_engine(tmp_path, "lsm")
    calls = {"n": 0}

    def fake_acquire(tokens):
        calls["n"] += 1
        if calls["n"] == 1:
            _time.sleep(0.02)  # straggling first read
        return Acquisition(nodes=[], reuse_tokens=32, device_tokens=0,
                           host_tokens=0, disk_tokens=32, io_s=0.0)

    eng.h.acquire = fake_acquire
    eng.h.release = lambda acq: None
    eng._ewma_read_s = 1e-4  # 0.02s >> 4 x 1e-4 -> hedge trips
    acq, dt, hedged = eng._acquire_hedged(list(range(64)))
    assert hedged
    assert calls["n"] == 2
    assert eng.stats.hedged_reads == 1
    assert dt < 0.02  # the retry won


# --------------------------------------- real-model serving loop (smoke size)
@pytest.fixture(scope="module")
def smoke_model():
    return RealModel(get_config("qwen3-14b", smoke=True), block_size=16)


def _serve_real(model, store, **sizes):
    with RuntimeServices(io_threads=2) as runtime:
        rep = serve_staged(model, store, runtime, prompt_len=128, **sizes)
    store.close()
    return rep


def test_real_model_stage_hit_rates_meet_expectation(tmp_path, smoke_model):
    rep = _serve_real(smoke_model, KVBlockStore(str(tmp_path / "lsm"), block_size=16))
    assert [st.expected_hit for st in rep.stages] == [0.0, 0.5, 0.75]
    for st in rep.stages:
        assert abs(st.hit - st.expected_hit) <= 0.05
        assert st.compute_s > 0
    assert rep.compiles_after_warmup == 0
    assert len(rep.decoded) == 8
    assert np.isfinite(np.asarray(rep.logits, np.float32)).all()


@pytest.mark.parametrize("codec", ["raw", "int8-zlib"])
def test_blocks_read_back_at_a_disk_hit_match_a_fresh_prefill(tmp_path, smoke_model, codec):
    """Budgets far below the corpus push hits to disk; what the store
    returns for a hit prompt is the prefill's own blocks — bit for bit
    through the raw codec, within the int8 bound through the default."""
    if codec == "raw":
        bc = BatchCodec(CODEC_RAW, use_zlib=False)
    else:
        bc = BatchCodec(CODEC_INT8, use_zlib=True)
    store = KVBlockStore(str(tmp_path / "lsm"), block_size=16, codec=bc)
    rep = _serve_real(smoke_model, store, device_blocks=4, host_blocks=8)
    assert rep.tokens_hit["disk"] > 0
    assert len(rep.stored_blocks) == len(rep.fresh_blocks) == 128 // 16
    for s, f in zip(rep.stored_blocks, rep.fresh_blocks):
        assert s.dtype == f.dtype == np.float16 and s.shape == f.shape
        if codec == "raw":
            np.testing.assert_array_equal(s.view(np.uint16), f.view(np.uint16))
        else:
            err = np.abs(s.astype(np.float32) - f.astype(np.float32))
            assert (err <= int8_error_bound(f)).all()


def test_cluster_node_imports_no_jax():
    """Node processes must not load JAX: the serving process alone holds
    the chip, and a child that touched it would fail or hang."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run(
        [sys.executable, "-c", "import repro.cluster.node, sys; assert 'jax' not in sys.modules"],
        env=env, timeout=60)
    assert proc.returncode == 0


# ------------------------------------------- the per-request timeline
def _staged_requests(seed: int, stages=(0.0, 0.9)):
    wl = StagedWorkload(prompt_len=128, requests_per_stage=6, stages=stages, block_size=16,
                        corpus_size=4, seed=seed)
    return wl, [r for si in range(len(stages)) for r in wl.stage_requests(si)]


def test_pipelined_record_parts_add_up_to_step_return_minus_submit(tmp_path, smoke_model):
    """queue_s + io_s + compute + commit + batch_wait_s is what a client
    waits: submit to the return of the step() that returned the request."""
    smoke_model.warmup(128, 0)
    store = KVBlockStore(str(tmp_path / "lsm"), block_size=16)
    with RuntimeServices(io_threads=2) as runtime:
        h = CacheHierarchy(16, 8, 16, store=store)
        eng = ServingEngine(h, ComputeModel(smoke_model.cfg), kv_bytes_per_token=512,
                            max_batch_tokens=512, real_prefill=smoke_model, runtime=runtime)
        assert eng.pipeline
        wl, reqs = _staged_requests(seed=8)
        for p in wl.corpus:
            eng.submit(type("R", (), {"tokens": p, "rid": -1, "stage": -1})())
        eng.run()
        eng.drain()
        eng.stats.reset_means()
        submitted, waited = {}, {}
        for r in reqs:
            submitted[r.rid] = time.perf_counter()
            eng.submit(r)
        while len(waited) < len(reqs):
            recs = eng.step()
            t = time.perf_counter()
            for rec in recs:
                waited[rec.rid] = (rec, t - submitted[rec.rid])
        eng.drain()
    store.close()
    assert sum(1 for rec, _ in waited.values() if rec.prefetched) > 0
    assert sum(1 for rec, _ in waited.values() if "decode" in rec.spans) > 0
    for rec, client_ttft in waited.values():
        parts = (rec.queue_s + rec.io_s + rec.spans["compute"] + rec.spans["commit"]
                 + rec.batch_wait_s)
        assert parts == pytest.approx(client_ttft, abs=1e-3), rec
        assert min(rec.queue_s, rec.batch_wait_s) >= 0
        assert rec.ttft_s == pytest.approx(rec.io_s + rec.compute_s)
        assert {"plan", "compute", "commit", "prefill", "device_to_host",
                "to_blocks"} <= set(rec.spans)
    # the TTFT histogram and mean observe what the client waited
    snap = eng.metrics_snapshot()
    hist = snap["histograms"]["repro_engine_ttft_seconds"]
    assert hist["count"] == len(reqs) + len(wl.corpus)
    client = [w for _, w in waited.values()]
    assert eng.stats.mean_ttft == pytest.approx(np.mean(client), abs=1e-3)
    assert snap["gauges"]["repro_engine_mean_ttft_s"] == eng.stats.mean_ttft
    assert not [k for k in snap["histograms"] if k.startswith("repro_engine_span_seconds_")]


def test_real_model_blocks_bit_identical_to_a_float32_copy(smoke_model):
    """The copy in the cache's own dtype, then the cast, hands back the
    blocks the copy straight to float32 did."""
    tokens = list(range(3, 131))
    blocks, _ = smoke_model(tokens, 32)
    _, cache = smoke_model._prefill_into(tokens, len(tokens))
    kv = np.concatenate([np.asarray(cache["k"], np.float32)[:, 0],
                         np.asarray(cache["v"], np.float32)[:, 0]])
    rows = kv.transpose(1, 0, 2, 3).reshape(kv.shape[1], -1)
    want = [rows[i * 16:(i + 1) * 16].astype(np.float16) for i in range(2, 128 // 16)]
    assert len(blocks) == len(want) == 6
    for got, exp in zip(blocks, want):
        assert got.dtype == np.float16
        np.testing.assert_array_equal(got.view(np.uint16), exp.view(np.uint16))


def _float32_copy_blocks(model, tokens, reused):
    """The host path the device layout replaced: the cache copied in bf16,
    cast to float32, concatenated, transposed, cut and cast to float16."""
    _, cache = model._prefill_into(tokens, len(tokens))
    kv = np.concatenate([np.asarray(cache["k"]).astype(np.float32)[:, 0],
                         np.asarray(cache["v"]).astype(np.float32)[:, 0]])
    rows = kv.transpose(1, 0, 2, 3).reshape(kv.shape[1], -1)
    return [rows[i * 16:(i + 1) * 16].astype(np.float16)
            for i in range(reused // 16, len(tokens) // 16)]


@pytest.mark.parametrize("reused", [0, 32, 40, 128 - 16])
def test_device_laid_out_blocks_match_the_host_path_and_own_their_data(smoke_model, reused):
    """Rows laid out on the device give the host path's blocks bit for bit,
    each an owned C-contiguous float16 array; a new ``reused`` at the same
    prompt length compiles nothing."""
    tokens = list(range(5, 133))
    smoke_model(tokens, 0)
    with CompileCounter() as counter:
        blocks, dt = smoke_model(tokens, reused)
    assert counter.compiles == 0
    assert dt > 0
    want = _float32_copy_blocks(smoke_model, tokens, reused)
    assert len(blocks) == len(want) == 8 - reused // 16
    cols = 2 * smoke_model.cfg.n_layers * smoke_model.cfg.n_kv_heads * smoke_model.cfg.d_head
    for got, exp in zip(blocks, want):
        assert got.dtype == np.float16 and got.shape == (16, cols)
        assert got.flags.c_contiguous
        assert got.flags.owndata or got.base.nbytes <= got.nbytes
        np.testing.assert_array_equal(got.view(np.uint16), exp.view(np.uint16))


def _host_events(xplane: str):
    """(line, name, start_ns, end_ns) of every ``repro.*`` event on a host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out.extend(((plane.name, i), ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events if ev.name.startswith("repro."))
    return out


def _inside(ev, outer):
    return [o for o in outer if o[0] == ev[0] and o[2] <= ev[2] and ev[3] <= o[3]]


def test_program_spans_nest_in_a_cpu_profiler_trace(tmp_path, smoke_model):
    import glob

    import jax

    smoke_model.warmup(128, 0)
    h = CacheHierarchy(16, 64, 128, store=KVBlockStore(str(tmp_path / "lsm"), block_size=16))
    eng = ServingEngine(h, ComputeModel(smoke_model.cfg), kv_bytes_per_token=512,
                        max_batch_tokens=512, real_prefill=smoke_model)
    _, reqs = _staged_requests(seed=9, stages=(0.0,))
    for r in reqs[:3]:
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        recs = eng.run()
    finally:
        jax.profiler.stop_trace()
    h.store.close()
    assert len(recs) == 3
    (xplane,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True)
    evs = _host_events(xplane)
    by = {n: [e for e in evs if e[1] == f"repro.{n}"]
          for n in ("step", "compute", "prefill", "device_to_host", "to_blocks", "commit")}
    assert by["step"] and len(by["compute"]) == 3 and len(by["commit"]) == 3
    for c in by["compute"] + by["commit"]:
        assert _inside(c, by["step"]), c
    for n in ("prefill", "device_to_host", "to_blocks"):
        assert len(by[n]) == 3
        for e in by[n]:
            assert _inside(e, by["compute"]), e
