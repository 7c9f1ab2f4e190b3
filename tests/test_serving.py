"""Serving engine + workload: staged hit rates realized, TTFT accounting,
hedged reads, LSM-vs-baseline ordering on a miniature workload."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cache.hierarchy import CacheHierarchy
from repro.configs import get_config
from repro.core.baselines import FilePerObjectStore, MemoryOnlyStore
from repro.core.codec import CODEC_INT8, CODEC_RAW, BatchCodec, int8_error_bound
from repro.core.store import KVBlockStore
from repro.runtime import RuntimeServices
from repro.serving import ComputeModel, ServingEngine
from repro.serving.real_model import RealModel, serve_staged
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
