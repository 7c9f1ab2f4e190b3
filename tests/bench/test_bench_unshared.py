"""The ``unshared`` mix over qwen3-14b, rehearsed at smoke widths on the CPU:
prompts that share nothing, so the check reads back from the store every
block the write-behind acknowledged, and a fault on that path makes the
run incorrect."""

import time

import pytest
from test_bench_rehearsal import FAULTS, RATE

from bench import harness, spec
from repro.core.sharded_store import ShardedKVBlockStore

CONFIG, MIX = "qwen3-14b", "unshared"
SEED = 2**31 + 41  # past 32 signed bits, as runs are given


def _run(seed, control=False):
    return harness.run(spec.config(CONFIG), spec.traffic(MIX), spec.limits(CONFIG), seed, 2.0,
                       RATE, time.perf_counter(), rehearse=True, control=control)


def test_rehearsal_of_the_unshared_mix_is_correct():
    res = _run(SEED)
    assert res.correct and res.failed == 0 and res.attempted > 0, res.compared
    assert res.e2e["prompt_tokens_per_s"] > 0 and res.stamps["compiles_in_window"] == 0
    # nothing shared, so no cached share; the store's read-back is compared
    assert {c["name"] for c in res.compared} == {
        "kv_prefill_rel_err", "kv_served_rel_err", "decode_logits_rel_err",
        "over_reused_requests", "unanswered_requests"}
    # every block of every compared prompt is read back from the store
    assert res.stamps["compared_served_blocks"] == res.stamps["compared_blocks"] > 0


def test_control_in_the_programs_place_is_not_correct():
    res = _run(SEED + 3, control=True)
    assert res.correct, res.compared
    assert res.control_correct is False, res.control_compared
    assert "kv_served_rel_err" in {c["name"] for c in res.control_compared}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_on_the_write_path_makes_the_run_incorrect(fault, monkeypatch):
    owner, attr, make = FAULTS[fault]
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))
    res = _run(SEED + 2)
    assert not res.correct, res.compared


def _first_half_stored(orig):
    def put_batch(self, tokens, blocks, start_block=0, skip_existing=True):
        return orig(self, tokens, list(blocks)[:len(blocks) // 2], start_block=start_block,
                    skip_existing=skip_existing)
    return put_batch


def _one_prompt_stored(orig):
    kept = []

    def put_batch(self, tokens, blocks, start_block=0, skip_existing=True):
        head = tuple(tokens[:self.block_size])
        kept[:] = kept or [head]
        if head != kept[0]:
            return 0
        return orig(self, tokens, blocks, start_block=start_block, skip_existing=skip_existing)
    return put_batch


STORE_FAULTS = {
    # the write-behind acknowledges a batch and stores only its first half
    "first_half_stored": _first_half_stored,
    # it stores the first prompt it is given and no other
    "one_prompt_stored": _one_prompt_stored,
}


@pytest.mark.parametrize("fault", sorted(STORE_FAULTS))
def test_blocks_the_store_drops_make_the_run_incorrect(fault, monkeypatch):
    make = STORE_FAULTS[fault]
    monkeypatch.setattr(ShardedKVBlockStore, "put_batch",
                        make(ShardedKVBlockStore.put_batch))
    res = _run(SEED + 4)
    assert not res.correct, res.compared
    served = {c["name"]: c for c in res.compared}["kv_served_rel_err"]
    assert served["value"] == 1.0 and not served["ok"]
