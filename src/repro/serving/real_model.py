"""Real compute for the serving engine, and the staged serving loop.

``RealModel`` holds one model at a config's widths, with weights made from a
seed, and its jitted prefill and decode on JAX's default device.  Passed as
the engine's ``real_prefill`` hook it prefills the whole prompt and copies
the KV blocks past the reused prefix to the host:
``(tokens, reused) -> (blocks, seconds)``.

``serve_staged`` drives one ``ServingEngine`` with it over a given store:
compile warm-up, write-through warm-up of a corpus, staged rounds at
expected hit rates, a drain (which raises write-behind and maintenance
errors), a read-back of one hit prompt's blocks from the store beside a
fresh prefill of that prompt, and a short greedy decode.
``examples/serve_e2e.py`` runs it at the smoke size; ``chip_smoke.py`` runs
it at published widths on a TPU.

Block layout: block i of a prompt holds tokens [i*B, (i+1)*B) in float16.
Row t is token t; the columns are (k|v, layer, kv-head, d_head).  The int8
codec's per-column scales are then per channel over the block's tokens.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.hierarchy import AcquirePlan, CacheHierarchy
from ..configs.base import ModelConfig
from ..models import api
from ..runtime import RuntimeServices
from ..workload import Request, StagedWorkload
from .compute_model import ComputeModel
from .engine import ServingEngine

REPO_ROOT = Path(__file__).resolve().parents[3]

# the staged workload every caller serves: stage hit rates, requests a
# stage, corpus prompts, and greedy tokens decoded at the end
STAGES = (0.0, 0.5, 0.75)
REQUESTS_PER_STAGE = 6
CORPUS = 8
DECODE_TOKENS = 8

# what JAX records around each XLA compile or persistent-cache retrieval
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> Optional[str]:
    """Give JAX's persistent compilation cache a fixed home before the first
    compile, and return it.  ``JAX_COMPILATION_CACHE_DIR``, where set, is
    left to JAX; otherwise the cache goes to ``<repo>/.jax_cache/`` on an
    accelerator.  On the CPU nothing is set (returns None): XLA:CPU entries
    read back log a machine-feature mismatch, and the programs are cheap."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts, while active, the XLA compiles JAX runs (a persistent-cache
    retrieval counts as one) and the seconds they take."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class RealModel:
    """A seeded model and its jitted prefill/decode; the engine's
    ``real_prefill`` hook."""

    def __init__(self, cfg: ModelConfig, block_size: int, seed: int = 0):
        if set(api.cache_specs(cfg, 1, block_size)) != {"k", "v"}:
            raise ValueError(f"{cfg.name}: blocks are built from a K/V cache only")
        enable_compile_cache()
        self.cfg = cfg
        self.block_size = block_size
        # one program: eager init compiles one per leaf shape and holds a
        # float32 copy of the largest leaf on the device
        self.params = jax.jit(api.init_params, static_argnums=0)(cfg, jax.random.key(seed))
        self._prefill = jax.jit(api.prefill_fn(cfg))
        self._decode = jax.jit(api.decode_fn(cfg))

    def _prefill_into(self, tokens: Sequence[int], max_seq: int):
        toks = jnp.asarray(np.asarray(tokens, np.int32)[None, :])
        cache = api.init_cache(self.cfg, 1, max_seq)
        return self._prefill(self.params, {"tokens": toks}, cache, 0)

    def __call__(self, tokens: Sequence[int], reused: int) -> Tuple[List[np.ndarray], float]:
        """Prefill the whole prompt; return the float16 blocks past the
        ``reused`` prefix and the seconds the prefill took on the device."""
        t0 = time.perf_counter()
        logits, cache = self._prefill_into(tokens, len(tokens))
        jax.block_until_ready((logits, cache))
        dt = time.perf_counter() - t0
        B = self.block_size
        kv = np.concatenate([np.asarray(cache["k"], np.float32)[:, 0],
                             np.asarray(cache["v"], np.float32)[:, 0]])  # (2L, S, KVH, Dh)
        rows = kv.transpose(1, 0, 2, 3).reshape(kv.shape[1], -1)
        blocks = [rows[i * B:(i + 1) * B].astype(np.float16)
                  for i in range(reused // B, len(tokens) // B)]
        return blocks, dt

    def generate(self, tokens: Sequence[int], n_new: int) -> Tuple[List[int], jax.Array]:
        """Prefill ``tokens``, then decode ``n_new`` greedy tokens.  Returns
        the tokens and the logits of the last decode step."""
        S = len(tokens)
        logits, cache = self._prefill_into(tokens, S + n_new)
        out: List[int] = []
        for i in range(n_new):
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            logits, cache = self._decode(self.params, nxt, cache, S + i)
            out.append(int(np.asarray(nxt)[0, 0]))
        return out, logits

    def warmup(self, prompt_len: int, decode_tokens: int) -> None:
        """Compile every program ``serve_staged`` runs at these sizes."""
        toks = list(range(prompt_len))
        self(toks, 0)
        self.generate(toks, decode_tokens)


@dataclass
class StageResult:
    expected_hit: float
    hit: float
    ttft_s: float
    io_s: float
    compute_s: float


@dataclass
class ServeReport:
    stages: List[StageResult]
    tokens_hit: Dict[str, int]  # by the hierarchy's tier names
    # the last stage's best-hit prompt: its blocks as the store returns
    # them, and from a fresh prefill
    stored_blocks: List[np.ndarray]
    fresh_blocks: List[np.ndarray]
    decoded: List[int]
    logits: jax.Array  # of the last decode step
    warmup_compiles: int
    warmup_compile_s: float
    compiles_after_warmup: int
    runtime: Dict


def _stored_blocks(h: CacheHierarchy, tokens: Sequence[int]) -> List[np.ndarray]:
    """The blocks of ``tokens`` the store holds, read through the
    hierarchy's fetch (probe, then batched or streamed get) as a disk hit
    reads them for fulfill."""
    B = h.block_size
    plan = AcquirePlan(tokens=list(tokens), chain_blocks=0, disk_chain_depth=0,
                       total_blocks=len(tokens) // B)
    fetched = h.fetch(plan)
    blocks = list(fetched.blocks[: fetched.probed_tokens // B])
    close = getattr(fetched.blocks, "close", None)
    if close is not None:
        close()
    return blocks


def serve_staged(
    model: RealModel,
    store,
    runtime: RuntimeServices,
    *,
    prompt_len: int,
    device_blocks: int = 64,
    host_blocks: int = 128,
    seed: int = 0,
) -> ServeReport:
    """Serve a staged workload through engine -> hierarchy -> ``store`` with
    ``model``'s real prefill.  ``store`` must run on ``runtime``'s executor
    where it takes one; the caller closes both."""
    B = model.block_size
    with CompileCounter() as warm:
        model.warmup(prompt_len, DECODE_TOKENS)
    h = CacheHierarchy(B, device_blocks, host_blocks, store=store)
    eng = ServingEngine(h, ComputeModel(model.cfg),
                        kv_bytes_per_token=model.cfg.kv_bytes_per_token,
                        max_batch_tokens=2048, real_prefill=model, runtime=runtime)
    wl = StagedWorkload(prompt_len=prompt_len, requests_per_stage=REQUESTS_PER_STAGE,
                        stages=STAGES, block_size=B, corpus_size=CORPUS, seed=seed)
    results: List[StageResult] = []
    hit_prompt: Optional[List[int]] = None
    with CompileCounter() as after:
        # write-through warm-up of the corpus (paper §4.1), settled on disk
        for p in wl.corpus:
            eng.submit(Request(-1, -1, p, 0.0))
        eng.run()
        eng.drain()
        for si, expected in enumerate(STAGES):
            reqs = wl.stage_requests(si)
            for r in reqs:
                eng.submit(r)
            recs = eng.run()
            results.append(StageResult(
                expected_hit=expected,
                hit=float(np.mean([r.reused_tokens / r.prompt_len for r in recs])),
                ttft_s=float(np.mean([r.ttft_s for r in recs])),
                io_s=float(np.mean([r.io_s for r in recs])),
                compute_s=float(np.mean([r.compute_s for r in recs])),
            ))
            best = max(recs, key=lambda rec: rec.reused_tokens)
            if best.reused_tokens:
                hit_prompt = next(r.tokens for r in reqs if r.rid == best.rid)
        eng.drain()
        stored = _stored_blocks(h, hit_prompt) if hit_prompt else []
        fresh = model(hit_prompt, 0)[0] if hit_prompt else []
        decoded, logits = model.generate(wl.corpus[0], DECODE_TOKENS)
    return ServeReport(
        stages=results,
        tokens_hit={"device": h.stats.tokens_hit_device, "host": h.stats.tokens_hit_host,
                    "disk": h.stats.tokens_hit_disk},
        stored_blocks=stored,
        fresh_blocks=fresh,
        decoded=decoded,
        logits=logits,
        warmup_compiles=warm.compiles,
        warmup_compile_s=warm.seconds,
        compiles_after_warmup=after.compiles,
        runtime=eng.runtime_report(),
    )
