"""The readers of the program's per-request timeline: a mean over the
window's records where the program stamps the field or span, and nothing,
without raising, from a program that records neither."""

from types import SimpleNamespace

import pytest

from bench import spec

# reader -> what it reads off a record
READS = {
    "engine.queue_s": lambda r: r.queue_s,
    "engine.batch_wait_s": lambda r: r.batch_wait_s,
    "model.device_to_host_s": lambda r: r.spans["device_to_host"],
    "model.to_blocks_s": lambda r: r.spans["to_blocks"],
    "store.decode_s": lambda r: r.spans["decode"],
    "hierarchy.commit_s": lambda r: r.spans["commit"],
}
NAMES = sorted(READS) + sorted(f"{n}.tail" for n in READS)


def _window(records):
    return SimpleNamespace(records=records)


def _record(i, decode=True):
    spans = {"compute": 0.5, "commit": 0.01 * i, "device_to_host": 0.02 * i,
             "to_blocks": 0.03 * i}
    if decode:
        spans["decode"] = 0.1 * i
    return SimpleNamespace(queue_s=0.2 * i, batch_wait_s=0.3 * i, spans=spans, io_s=0.0)


@pytest.mark.parametrize("name", NAMES)
def test_reader_takes_the_mean_where_the_program_records_it(name):
    read = spec.reader(name)
    recs = [_record(1), _record(2), _record(3, decode=False)]
    base = name.removesuffix(".tail")
    got = [READS[base](r) for r in recs if base != "store.decode_s" or "decode" in r.spans]
    assert read(_window(recs)) == pytest.approx(sum(got) / len(got))


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_older_program(name):
    # a record as the program wrote it before it kept a timeline
    old = SimpleNamespace(rid=1, prompt_len=64, io_s=0.1, compute_s=0.2, ttft_s=0.3)
    assert spec.reader(name)(_window([old])) is None
    assert spec.reader(name)(_window([])) is None


def test_each_reader_is_a_metric_of_its_cell():
    per_layer = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in NAMES:
        m = per_layer[name]
        tail = name.endswith(".tail")
        assert m["source"] == "program_counter" and m["unit"] == "s" and m["better"] == "lower"
        assert m["moves"] == ("ttft_p90_s" if tail else "ttft_p50_s")
        assert m["workloads"] == ["glm4-9b.chat-shared" if tail else "qwen3-14b.chat-shared"]
