"""End-to-end serving driver: serve a real (reduced) model with batched
requests through the full stack —

    staged workload -> ServingEngine (two-stage pipeline on the runtime's
                       I/O executor; write-behind commits; off-path
                       maintenance)
                    -> CacheHierarchy (radix + tiers; plan/fetch/fulfill)
                    -> ShardedKVBlockStore (N independent LSM shards with
                       parallel fan-out, real disk; any StorageBackend
                       slots in here)
                    -> real prefill/decode on the smoke model

KV blocks written to / promoted from the disk tier are the model's actual
cache tensors; TTFT here is fully measured (real compute + real I/O), and
batch k+1's disk promotions run while batch k computes.  The serving loop
is ``repro.serving.real_model.serve_staged``, the one ``chip_smoke.py`` runs
at published widths on a TPU.

    PYTHONPATH=src python examples/serve_e2e.py
"""

import tempfile

import numpy as np

from repro.configs import get_config
from repro.core.sharded_store import ShardedKVBlockStore
from repro.runtime import RuntimeServices
from repro.serving.real_model import RealModel, serve_staged

ARCH = "qwen3-14b"
BLOCK = 16
PROMPT = 128
N_SHARDS = 4


def main():
    model = RealModel(get_config(ARCH, smoke=True), BLOCK)
    with RuntimeServices(io_threads=4) as runtime:
        store = ShardedKVBlockStore(tempfile.mkdtemp(prefix="serve_e2e_"), n_shards=N_SHARDS,
                                    block_size=BLOCK, io_executor=runtime.executor)
        print(f"serving {ARCH} (reduced) — real prefill, real disk tier")
        rep = serve_staged(model, store, runtime, prompt_len=PROMPT)
        for si, st in enumerate(rep.stages):
            print(f"stage {si} (expect hit {st.expected_hit:.2f}): hit {st.hit:.2f}, "
                  f"TTFT {st.ttft_s*1e3:.1f}ms (io {st.io_s*1e3:.1f}ms, "
                  f"compute {st.compute_s*1e3:.1f}ms)")
        err = max(float(np.abs(s.astype(np.float32) - f.astype(np.float32)).max())
                  for s, f in zip(rep.stored_blocks, rep.fresh_blocks))
        print(f"hit prompt: {len(rep.stored_blocks)} blocks read back from the store, "
              f"max |stored - fresh prefill| {err:.4g}")
        print(f"decoded {len(rep.decoded)} tokens: {rep.decoded}")
        print(f"compiles: warm-up {rep.warmup_compiles} ({rep.warmup_compile_s:.2f}s), "
              f"after warm-up {rep.compiles_after_warmup}")
        print(f"store: shards={store.n_shards} files/shard={store.shard_file_counts()} "
              f"bytes={store.disk_bytes} compression={store.stats.compression_ratio:.2f}x "
              f"hit-tiers d/h/d={rep.tokens_hit['device']}/{rep.tokens_hit['host']}/"
              f"{rep.tokens_hit['disk']}")
        r = rep.runtime
        print(f"runtime: prefetched={r['prefetched_requests']} "
              f"(ready on arrival {r['prefetch_ready']}) overlap={r['overlap_io_s']*1e3:.1f}ms "
              f"writeback_blocks={r['writeback_blocks']} "
              f"maintenance_runs={r['maintenance_runs']}")
    store.close()
    print("ok")


if __name__ == "__main__":
    main()
