"""A configuration brings its model family as a file: ``spec.family`` loads
``bench/families/<name>.py`` by the name the configuration gives (``gqa``
where it gives none), and the harness takes the program's config, the
smoke widths, the block parts, the reference rows, the FLOPs and the scope
costs from that module alone."""

import shutil
import time
from pathlib import Path

import pytest

from bench import harness, spec

FIXTURE = Path(__file__).parent / "fixtures" / "families" / "recording.py"
RATE = 3.0  # some six requests in the 2 s window


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURE, bench / "families" / FIXTURE.name)
    monkeypatch.setattr(spec, "BENCH", bench)
    return bench


def test_family_found_by_name(bench_copy):
    fam = spec.family({"bench_family": "recording"})
    assert fam.__file__ == str(bench_copy / "families" / "recording.py")
    assert spec.family({"bench_family": "recording"}) is fam  # loaded once
    assert spec.family({}).__file__ == str(bench_copy / "families" / "gqa.py")
    assert spec.family(spec.config("qwen3-14b")).__file__ == str(bench_copy / "families" / "gqa.py")
    with pytest.raises(FileNotFoundError):
        spec.family({"bench_family": "no-such-family"})


def _run(cfg, seed):
    return harness.run(cfg, spec.traffic("chat-shared"), spec.limits("qwen3-14b"), seed, 2.0,
                       RATE, time.perf_counter(), rehearse=True)


def test_harness_takes_the_model_from_its_family(bench_copy):
    cfg = dict(spec.config("qwen3-14b"), bench_family="recording")
    fam = spec.family(cfg)
    fam.CALLS.clear()
    res = _run(cfg, 9)
    assert res.correct, res.compared
    assert set(fam.CALLS) == {"rehearsal_config", "model_config", "block_parts", "prefill_flops",
                              "scope_costs", "init_weights", "forward"}
    n = len(res.window.hook_calls)
    assert n > 0
    assert res.window.required_flops == fam.FLOPS_PER_CALL * n
    assert res.window.scope_costs == {"attention": (fam.SCOPE_COST[0] * n, fam.SCOPE_COST[1] * n)}
    # the rows the program's blocks are held to are the family's
    fam.ROW_SCALE = 1.5
    res = _run(cfg, 9)
    assert not res.correct
    assert res.numbers["kv_prefill_rel_err"] == pytest.approx(0.5 / 1.5, abs=2e-3)
