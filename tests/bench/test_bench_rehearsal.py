"""A whole run of a cell at smoke widths on the CPU, past the harness's look
for a chip: it comes out correct, and with the served path broken
underneath, each way a cell can break, ``correct`` comes out false."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, spec
from repro.cache.hierarchy import CacheHierarchy
from repro.core.sharded_store import ShardedKVBlockStore
from repro.serving.real_model import RealModel

CELL = "qwen3-14b.chat-shared"
ROOT = spec.ROOT
# a fixed load, so the 2 s window holds some six requests whatever the cell's rate
RATE = 3.0


def _run(seed: int = 5, control: bool = False):
    cell = spec.cell(CELL)
    return harness.run(spec.config(cell["config"]), spec.traffic(cell["traffic"]),
                       spec.limits(cell["config"]), seed, 2.0, RATE,
                       time.perf_counter(), rehearse=True, control=control)


def test_rehearsal_is_correct_and_reads_every_counter_metric():
    res = _run()
    assert res.correct, res.compared
    assert res.attempted > 0 and res.failed == 0
    assert res.stamps["compiles_in_window"] == 0
    assert res.stamps["compared_requests"] >= 2
    for m in spec.metrics_of(CELL, "per_layer"):
        value = spec.reader(m["name"])(res.window)
        if m["source"] == "device_trace":
            assert value is None  # no trace in this run: the reader finds nothing
        else:
            assert value is not None and value >= 0, m["name"]
    assert res.e2e["ttft_p90_s"] >= res.e2e["ttft_p50_s"] > 0


def test_control_in_the_programs_place_is_not_correct():
    res = _run(seed=7, control=True)
    assert res.correct, res.compared
    assert res.control_correct is False, res.control_compared
    names = {c["name"] for c in res.compared}
    assert {c["name"] for c in res.control_compared} == names
    failed = {c["name"] for c in res.control_compared if not c["ok"]}
    assert failed <= {"kv_prefill_rel_err", "kv_served_rel_err", "decode_logits_rel_err"}


def _zeros(orig):
    def call(self, tokens, reused):
        blocks, dt = orig(self, tokens, reused)
        return [np.zeros_like(b) for b in blocks], dt
    return call


def _half(orig):
    def call(self, tokens, reused):
        blocks, dt = orig(self, tokens, reused)
        return blocks[:len(blocks) // 2], dt
    return call


def _altered(orig):
    def call(self, tokens, reused):
        blocks, dt = orig(self, tokens, reused)
        blocks = list(blocks)
        if blocks:
            blocks[len(blocks) // 2] = blocks[len(blocks) // 2] * np.float16(1.5)
        return blocks, dt
    return call


def _store_altered(orig):
    def get(self, tokens, n_tokens):
        return [b * np.float16(1.25) for b in orig(self, tokens, n_tokens)]
    return get


def _no_commit(orig):
    def commit(self, tokens, new_blocks, acq):
        return None
    return commit


FAULTS = {
    # a step that hands back the cache as it was before it ran
    "state_unchanged": (RealModel, "__call__", _zeros),
    # half of the prompt's new blocks left out
    "half_left_out": (RealModel, "__call__", _half),
    # an answer altered where it is produced
    "block_altered": (RealModel, "__call__", _altered),
    # an answer altered where the store produces it
    "store_altered": (ShardedKVBlockStore, "get_batch", _store_altered),
    # caching stopped: nothing is committed, nothing is reused
    "nothing_cached": (CacheHierarchy, "commit", _no_commit),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_underneath_makes_the_run_incorrect(fault, monkeypatch):
    owner, attr, make = FAULTS[fault]
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))
    res = _run(seed=6)
    assert not res.correct, res.compared


def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=240)


def test_cli_without_a_chip_exits_nonzero_with_no_result():
    p = _cli("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_cli_rehearsal_prints_no_device_metric():
    p = _cli("--workload", CELL, "--seed", "2", "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last and "device" not in last
    assert list(last)[-1] == "compared"
