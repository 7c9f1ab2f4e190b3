"""Chip smoke test: serve qwen3-14b at its published widths on one TPU
through the normal path — engine -> cache hierarchy -> LSM store -> real
prefill on the chip — then the same engine over a two-node cache cluster.

    python chip_smoke.py [--seed N]

Phase "serve": a ``ShardedKVBlockStore`` on disk with its default codec
(int8+zlib).  Phase "cluster": a ``ClusterKVBlockStore`` over two local
node processes with the raw codec; the nodes import no JAX, so this process
alone holds the chip.  Both phases serve the staged workload of
``repro.serving.real_model.serve_staged`` and check what comes out: stage
hit rates, hits read back from disk, stored blocks against a fresh
prefill, finite logits, and no compile after warm-up.

Timings printed are host-clock seconds on the chip's host.  The
hierarchy's "device" tier is host memory; its tier names are printed as
the hierarchy names them.

Needs a TPU: where JAX finds none it exits nonzero and prints no result
line.  On success the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

import argparse
import dataclasses
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "qwen3-14b"
LAYERS = 8  # of 40: one stage of a five-stage pipeline
BLOCK = 16
PROMPT = 1024
N_SHARDS = 4
N_NODES = 2
HIT_TOLERANCE = 0.05


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


def check_phase(check, phase, rep, exact, dev, kind):
    import numpy as np

    from repro.core.codec import int8_error_bound
    from repro.serving.real_model import DECODE_TOKENS

    for i, st in enumerate(rep.stages):
        print(f"[{phase}] stage {i}: expected hit {st.expected_hit:.2f}, hit {st.hit!r}, "
              f"TTFT {st.ttft_s!r} s (io {st.io_s!r} s, compute {st.compute_s!r} s) "
              f"host clock, {kind} host", flush=True)
        check(f"{phase} stage {i} hit rate", abs(st.hit - st.expected_hit) <= HIT_TOLERANCE,
              f"{st.hit!r} vs expected {st.expected_hit} ± {HIT_TOLERANCE}")
    t = rep.tokens_hit
    print(f"[{phase}] tokens hit by hierarchy tier: device {t['device']}, host {t['host']}, "
          f"disk {t['disk']}", flush=True)
    check(f"{phase} hits from disk", t["disk"] > 0, f"{t['disk']} tokens")

    stored, fresh = rep.stored_blocks, rep.fresh_blocks
    whole = PROMPT // BLOCK
    same_count = len(stored) == len(fresh) == whole
    if exact:
        agree = same_count and all(
            s.dtype == f.dtype and np.array_equal(s.view(np.uint16), f.view(np.uint16))
            for s, f in zip(stored, fresh))
        how = "bit-identical"
    else:
        worst = max((float(np.max(np.abs(s.astype(np.float32) - f.astype(np.float32))
                                  / int8_error_bound(f)))
                     for s, f in zip(stored, fresh)), default=float("inf"))
        agree = same_count and worst <= 1.0
        how = f"worst |stored - fresh| / int8 bound = {worst!r}"
    check(f"{phase} stored blocks match a fresh prefill", agree,
          f"{len(stored)} blocks of {stored[0].shape if stored else None} "
          f"{stored[0].dtype if stored else ''} read back of {whole}, {how}")

    logits = rep.logits
    check(f"{phase} logits on the TPU", logits.devices() == {dev}, str(logits.devices()))
    check(f"{phase} logits finite", bool(np.isfinite(np.asarray(logits, np.float32)).all()),
          f"shape {logits.shape}")
    check(f"{phase} decoded tokens", len(rep.decoded) == DECODE_TOKENS, str(rep.decoded))
    print(f"[{phase}] warm-up compiles {rep.warmup_compiles} in {rep.warmup_compile_s!r} s",
          flush=True)
    check(f"{phase} compiles after warm-up", rep.compiles_after_warmup == 0,
          str(rep.compiles_after_warmup))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and workload")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    kind = dev.device_kind

    from repro.cluster.cluster_store import ClusterKVBlockStore
    from repro.cluster.node import spawn_local_node
    from repro.configs import get_config
    from repro.core.sharded_store import ShardedKVBlockStore
    from repro.runtime import RuntimeServices
    from repro.serving.real_model import CompileCounter, RealModel, enable_compile_cache, serve_staged

    cache_dir = enable_compile_cache()
    check = Checks()
    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    print(f"device: {dev.platform} {kind!r}, {len(jax.devices())} device(s); "
          f"compile cache {cache_dir}", flush=True)
    print(f"model: {ARCH} at published widths (d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"{cfg.n_kv_heads} KV heads, d_head {cfg.d_head}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}); {LAYERS} of 40 layers, standing for one of five pipeline "
          f"stages of the 40-layer model; random weights from seed {args.seed}", flush=True)

    with CompileCounter() as total:
        model = RealModel(cfg, BLOCK, seed=args.seed)
        params = jax.tree.leaves(model.params)
        check("params on the TPU", all(p.devices() == {dev} for p in params),
              f"{len(params)} arrays, {sum(p.nbytes for p in params)!r} bytes")
        print(f"blocks: {BLOCK} tokens x {cfg.kv_bytes_per_token} B = "
              f"{BLOCK * cfg.kv_bytes_per_token} B float16; prompt {PROMPT} tokens",
              flush=True)

        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            with RuntimeServices(io_threads=4) as runtime:
                store = ShardedKVBlockStore(os.path.join(work, "serve"), n_shards=N_SHARDS,
                                            block_size=BLOCK, io_executor=runtime.executor)
                rep = serve_staged(model, store, runtime, prompt_len=PROMPT, seed=args.seed)
            print(f"[serve] store: {N_SHARDS} shards, {store.disk_bytes} B on disk, "
                  f"compression {store.stats.compression_ratio!r}x", flush=True)
            store.close()
            check_phase(check, "serve", rep, exact=False, dev=dev, kind=kind)

            nodes = []
            try:
                nodes = [spawn_local_node(os.path.join(work, f"node_{i}"), block_size=BLOCK,
                                          codec="raw") for i in range(N_NODES)]
                cluster = ClusterKVBlockStore([n.address for n in nodes], block_size=BLOCK)
                with RuntimeServices(io_threads=4) as runtime:
                    rep = serve_staged(model, cluster, runtime, prompt_len=PROMPT,
                                       seed=args.seed)
                print(f"[cluster] {N_NODES} node processes, raw codec, "
                      f"{cluster.disk_bytes} B on disk", flush=True)
                cluster.close()
            finally:
                for n in nodes:
                    n.close()
            check_phase(check, "cluster", rep, exact=True, dev=dev, kind=kind)

    print(f"compile: {total.compiles} programs in {total.seconds!r} s, "
          f"{total.cache_hits} from the persistent cache", flush=True)
    if check.failed:
        print(f"chip_smoke: failed {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
