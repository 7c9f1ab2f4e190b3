"""The trace reduction: exact on hand-made events, on a recorded cut of a
traced run, and extraction from a trace recorded here on the CPU.

``fixtures/trace_cut_cpu_smoke.json`` is 0.6 s of a traced rehearsal
(qwen3-14b.chat-shared at smoke widths, on the CPU): the XLA operations of
the CPU's compute threads stand in for the device's, and the prefill's
dispatches for its program runs.  ``fixtures/trace_cut_tpu_glm4-9b.json``
is 0.6 s of a traced run of glm4-9b.chat-shared on a TPU v5 lite: the
device plane's ``XLA Ops`` and ``XLA Modules`` lines as the chip wrote
them."""

import glob
import json
import os

import pytest

from bench import trace

# every recorded cut: a CPU rehearsal's, and any cut of a chip trace added beside it
CUTS = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "fixtures", "trace_cut_*.json")))
TPU = "/device:TPU:0"


def test_reduce_by_hand():
    ev = trace.TraceEvents(
        ops={TPU: [("fusion.1", 1.0, 1.0), ("fusion.2", 1.5, 1.0),  # overlap: busy 1.0-2.5
                   ("convolution.3", 4.0, 0.5), ("fusion.1", 9.5, 1.0)]},  # clipped at 10
        modules={TPU: [("jit_prefill(1)", 1.0, 1.5), ("jit_prefill(2)", 4.0, 0.5),
                       ("jit_decode(3)", 9.5, 1.0)]},
        spans=[("bench.window", 0.0, 10.0), ("bench.engine_step", 0.6, 5.0),
               ("bench.prefill_hook", 0.8, 3.0), ("bench.wait_arrival", 6.0, 3.0),
               ("bench.fetch", 3.0, 4.0)])
    red = trace.reduce(ev)
    assert red.window_s == 10.0 and red.devices == 1
    assert red.busy_s == pytest.approx(1.5 + 0.5 + 0.5)
    assert red.idle_share == pytest.approx(1 - 2.5 / 10)
    assert red.program_s["prefill"] == pytest.approx(2.0)
    assert dict(red.top_ops) == pytest.approx({"fusion.1": 2.0, "fusion.2": 1.0,
                                              "convolution.3": 0.5})
    # gaps: 0-1 (mid 0.5: window only), 2.5-4 (mid 3.25: hook), 4.5-9.5 (mid 7: wait)
    assert dict(red.idle_gaps) == pytest.approx({"host": 1.0, "bench.prefill_hook": 1.5,
                                                "bench.wait_arrival": 5.0})


def test_reduce_without_device_ops_raises():
    ev = trace.TraceEvents(ops={TPU: [("fusion.1", 11.0, 1.0)]}, modules={},
                           spans=[("bench.window", 0.0, 10.0)])
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(ev)
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(trace.TraceEvents(spans=[("bench.window", 0.0, 10.0)]))


@pytest.mark.parametrize("fixture", CUTS, ids=os.path.basename)
def test_reduce_recorded_cut(fixture):
    with open(fixture) as f:
        ev = trace.TraceEvents.from_json(json.load(f))
    red = trace.reduce(ev)
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    busy = trace._union([(s, s + d) for _, s, d in ev.ops[TPU]])
    assert red.busy_s == pytest.approx(sum(b - a for a, b in trace._clip(busy, *_window(ev))))
    assert 0 < red.program_s["prefill"] <= red.window_s
    assert 3 <= len(red.top_ops) <= 10
    assert red.top_ops[0][1] >= red.top_ops[-1][1] > 0
    assert sum(s for _, s in red.idle_gaps) <= red.window_s - red.busy_s + 1e-9


def _window(ev):
    (lo, d), = [(s, d) for n, s, d in ev.spans if n == trace.WINDOW_SPAN]
    return lo, lo + d


def test_extract_host_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.engine_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.extract(trace.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev.spans]
    assert names.count("bench.window") == 1 and names.count("bench.engine_step") == 1
    (_, ws, wd), = [e for e in ev.spans if e[0] == "bench.window"]
    (_, ss, sd), = [e for e in ev.spans if e[0] == "bench.engine_step"]
    assert ws <= ss and ss + sd <= ws + wd
    assert ev.ops == {}  # no TPU plane on the CPU: nothing to read
