"""Shared benchmark harness: backend construction, staged-workload runs,
result tables.  Scales the paper's setup to this container (single CPU
core, small disk) while keeping every *mechanism* real: real files, real
LSM compaction, real compression, measured I/O.  Compute time is modeled
(A30 target) per DESIGN.md §7 and reported separately from I/O.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.cache.hierarchy import CacheHierarchy
from repro.configs import get_config
from repro.core.baselines import FilePerObjectStore, MemoryOnlyStore
from repro.core.codec import CODEC_INT8, CODEC_RAW, BatchCodec
from repro.core.sharded_store import ShardedKVBlockStore
from repro.core.store import KVBlockStore
from repro.serving import ComputeModel, ServingEngine
from repro.workload import PAPER_STAGES, StagedWorkload

ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts")


@dataclass
class BenchScale:
    """Container-scale defaults; --paper-scale multiplies everything up."""

    prompt_len: int = 1024
    requests_per_stage: int = 30
    stages: tuple = PAPER_STAGES
    corpus_size: int = 96
    kv_bytes_per_token: int = 1024
    block_size: int = 16
    warmup_tokens: int = 0  # 0 -> one pass over the corpus
    disk_budget_frac: float = 0.5  # of the raw corpus footprint
    mem_budget_frac: float = 0.06
    device_frac: float = 0.33  # of the memory budget


def _budgets(s: BenchScale):
    corpus_bytes = s.corpus_size * s.prompt_len * s.kv_bytes_per_token
    disk = int(corpus_bytes * s.disk_budget_frac)
    mem_blocks = max(
        8, int(corpus_bytes * s.mem_budget_frac) // (s.block_size * s.kv_bytes_per_token)
    )
    dev_blocks = max(4, int(mem_blocks * s.device_frac))
    host_blocks = mem_blocks - dev_blocks
    return disk, dev_blocks, host_blocks


def make_backend(root: str, kind: str, s: BenchScale, adaptive: bool = True):
    disk, _, _ = _budgets(s)
    if kind == "lsm":
        # controller window ~ one workload stage of ops so phase shifts are
        # visible to the drift detector (paper §3.3 sliding window)
        window = max(256, s.requests_per_stage * (s.prompt_len // s.block_size) // 2)
        store = KVBlockStore(
            os.path.join(root, "lsm"),
            block_size=s.block_size,
            codec=BatchCodec(CODEC_INT8, use_zlib=True),
            budget_bytes=disk,
            adaptive=adaptive,
            controller_window=window,
        )
        store.controller.min_ops_between_tunings = window // 4
        return store
    if kind == "lsm-sharded":
        window = max(256, s.requests_per_stage * (s.prompt_len // s.block_size) // 2)
        store = ShardedKVBlockStore(
            os.path.join(root, "lsm_sharded"),
            n_shards=4,
            block_size=s.block_size,
            codec=BatchCodec(CODEC_INT8, use_zlib=True),
            budget_bytes=disk,
            adaptive=adaptive,
            controller_window=window,
        )
        for shard in store.shards:
            # per-shard window was scaled down by 1/n_shards in the store
            shard.controller.min_ops_between_tunings = max(64, shard.controller.window // 4)
        return store
    if kind == "file":
        # file-per-object stores raw tensors (per-object compression defeats
        # batching — paper §3.4); same *physical* disk budget incl. fs slack
        return FilePerObjectStore(
            os.path.join(root, "file"),
            block_size=s.block_size,
            codec=BatchCodec(CODEC_RAW, use_zlib=False),
            budget_bytes=disk,
        )
    if kind == "memory":
        return None
    raise ValueError(kind)


def make_engine(root: str, kind: str, s: BenchScale, arch: str = "glm4-9b", adaptive=True):
    cfg = get_config(arch)
    store = make_backend(root, kind, s, adaptive)
    disk, dev_blocks, host_blocks = _budgets(s)
    h = CacheHierarchy(s.block_size, dev_blocks, host_blocks, store=store)
    return ServingEngine(
        h,
        ComputeModel(cfg),
        kv_bytes_per_token=s.kv_bytes_per_token,
        max_batch_tokens=8 * s.prompt_len,
    )


@dataclass
class StageResult:
    stage: int
    expected_hit: float
    hit_rate: float
    mean_ttft_s: float
    mean_io_s: float
    mean_compute_s: float


def run_staged(engine: ServingEngine, s: BenchScale, seed: int = 0) -> List[StageResult]:
    wl = StagedWorkload(
        prompt_len=s.prompt_len,
        requests_per_stage=s.requests_per_stage,
        stages=s.stages,
        block_size=s.block_size,
        corpus_size=s.corpus_size,
        seed=seed,
    )
    # ---- warmup: write-through population over the corpus (paper §4.1)
    warm = s.warmup_tokens or s.corpus_size * s.prompt_len
    for p in wl.warmup_prompts(warm):
        engine.submit(type("R", (), {"tokens": p, "rid": -1, "stage": -1})())
    engine.run()
    engine.stats.reset_means()

    out: List[StageResult] = []
    for si in range(len(s.stages)):
        recs = []
        for r in wl.stage_requests(si):
            engine.submit(r)
        recs = engine.run()
        out.append(
            StageResult(
                stage=si,
                expected_hit=s.stages[si],
                hit_rate=float(np.mean([r.reused_tokens / r.prompt_len for r in recs])),
                mean_ttft_s=float(np.mean([r.ttft_s for r in recs])),
                mean_io_s=float(np.mean([r.io_s for r in recs])),
                mean_compute_s=float(np.mean([r.compute_s for r in recs])),
            )
        )
    return out


def summarize(results: Dict[str, List[StageResult]]) -> Dict:
    rows = {}
    for kind, stages in results.items():
        rows[kind] = {
            "hit_rate": float(np.mean([st.hit_rate for st in stages])),
            "ttft_s": float(np.mean([st.mean_ttft_s for st in stages])),
            "io_s": float(np.mean([st.mean_io_s for st in stages])),
            "per_stage": [st.__dict__ for st in stages],
        }
    return rows


def percentiles(values, qs=(50, 95, 99)) -> Dict[str, float]:
    """Shared latency-quantile convention for every benchmark: p50/p95/p99
    by numpy's linear interpolation.  One helper so runtime_bench and
    cluster_bench (and anything after them) report comparable tails
    instead of each hand-rolling its own ``np.percentile`` call."""
    vals = list(values)
    if not vals:
        return {f"p{q}": 0.0 for q in qs}
    arr = np.asarray(vals, dtype=float)
    return {f"p{q}": float(np.percentile(arr, q)) for q in qs}


def save_artifact(name: str, payload: Dict) -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def fresh_dir(path: str) -> str:
    if os.path.exists(path):
        shutil.rmtree(path)
    os.makedirs(path)
    return path
