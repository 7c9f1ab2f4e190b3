"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Runs the full serving stack — staged workload -> radix/LSM cache hierarchy
-> continuous-batching engine — with the disk tier on real files.  Compute
is modelled (``serving/compute_model.py``: an A30 estimate) and I/O is
measured; every block stored is one random template.  The path with real
prefill, whose blocks are the model's own KV cache, is
``repro.serving.real_model.serve_staged``, run by ``examples/serve_e2e.py``
(smoke size) and ``chip_smoke.py`` (published widths, on a TPU).
"""

import argparse
import os
import tempfile


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--backend", default="lsm", choices=["lsm", "file", "memory"])
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--stages", default="0.2,0.5,0.7")
    ap.add_argument("--root", default=None)
    args = ap.parse_args()

    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    from benchmarks import common

    stages = tuple(float(x) for x in args.stages.split(","))
    s = common.BenchScale(
        prompt_len=args.prompt_len,
        requests_per_stage=args.requests,
        stages=stages,
        corpus_size=max(16, args.requests),
    )
    root = args.root or tempfile.mkdtemp(prefix="serve_")
    eng = common.make_engine(root, args.backend, s, arch=args.arch)
    results = common.run_staged(eng, s)
    print(f"[launch.serve] arch={args.arch} backend={args.backend} prompt={args.prompt_len}")
    print(f"{'stage':>5s} {'exp_hit':>8s} {'hit':>6s} {'TTFT(s)':>9s} {'IO(ms)':>8s}")
    for st in results:
        print(f"{st.stage:5d} {st.expected_hit:8.2f} {st.hit_rate:6.3f} "
              f"{st.mean_ttft_s:9.4f} {st.mean_io_s*1e3:8.2f}")
    if eng.h.store is not None:
        st = eng.h.store
        print(f"[store] files={st.file_count} disk={st.disk_bytes/1e6:.1f}MB "
              + (f"compression={st.stats.compression_ratio:.2f}x" if hasattr(st.stats, "compression_ratio") else ""))


if __name__ == "__main__":
    main()
