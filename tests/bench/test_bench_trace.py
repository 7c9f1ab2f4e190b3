"""The trace reduction: exact on hand-made events, on a recorded cut of a
traced run, and extraction from a trace recorded here on the CPU.

``fixtures/trace_cut_cpu_smoke.json`` is 0.6 s of a traced rehearsal
(qwen3-14b.chat-shared at smoke widths, on the CPU): the XLA operations of
the CPU's compute threads stand in for the device's, and the prefill's
dispatches for its program runs.  ``fixtures/trace_cut_tpu_glm4-9b.json``
is 0.6 s of a traced run of glm4-9b.chat-shared on a TPU v5 lite: the
device plane's ``XLA Ops`` and ``XLA Modules`` lines as the chip wrote
them.  ``fixtures/trace_cut_tpu_scope_probe.json`` is five runs on a TPU v5
lite of a program ``prefill_probe`` of five 4096 x 4096 bf16 matmuls, the
last four inside ``jax.named_scope("probe_scope")``: its operations carry
the scope paths the chip's trace gave them."""

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
    busy = trace._union([(s, s + d) for _, s, d, *_ in ev.ops[TPU]])
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


def test_scope_time_of_the_prefill_program_by_hand():
    path = "jit(prefill_rows)/{}/dot_general:"
    ev = trace.TraceEvents(
        ops={TPU: [("fusion.1", 1.0, 0.5, path.format("attention")),
                   ("fusion.2", 1.5, 0.25, path.format("moe")),
                   ("fusion.3", 1.75, 0.125, "jit(prefill_rows)/attention_out/add:"),  # no component
                   ("fusion.4", 2.0, 0.5, ""),  # no scope path
                   ("fusion.5", 5.0, 1.0, path.format("attention")),  # outside any prefill run
                   ("fusion.6", 8.0, 0.5, "jit(prefill_rows)/layer/attention/dot:")]},
        modules={TPU: [("jit_prefill_rows(1)", 1.0, 1.5), ("jit_decode(2)", 5.0, 1.0),
                       ("jit_prefill_rows(1)", 8.0, 1.0)]},
        spans=[("bench.window", 0.0, 10.0)])
    red = trace.reduce(ev, scopes=["attention", "moe", "mla"])
    assert red.scope_s == pytest.approx({"attention": 1.0, "moe": 0.25, "mla": 0.0})
    assert red.program_s["prefill"] == pytest.approx(2.5)
    assert trace.reduce(ev).scope_s == {}


def test_scope_time_on_a_chip_cut():
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "trace_cut_tpu_scope_probe.json")) as f:
        ev = trace.TraceEvents.from_json(json.load(f))
    red = trace.reduce(ev, scopes=["probe_scope"])
    # the runs the reduction counts: those that start in the window (the first
    # starts on the device's clock a millisecond before the host's window span)
    lo, hi = _window(ev)
    runs = [(s, s + d) for _, s, d in ev.modules[TPU] if lo <= s < hi]
    assert len(runs) == 4
    inside = sum(d for _, s, d, path in ev.ops[TPU]
                 if "probe_scope" in path.split("/") and any(a <= s < b for a, b in runs))
    assert red.scope_s["probe_scope"] == pytest.approx(inside) and inside > 0
    # four of the five matmuls, which take the program's time between them
    assert 0.75 < red.scope_s["probe_scope"] / red.program_s["prefill"] < 0.85


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def test_op_scopes_from_the_xspace_protobuf(tmp_path):
    """An ``XSpace`` written field by field as xplane.proto numbers them: one
    operation's scope as a string, one as a reference to a stat name, one
    with none, and a host plane that is not read."""
    scope = "jit(prefill_rows)/attention/dot_general:"
    stat_meta = [_msg((1, 3), (2, "tf_op")), _msg((1, 4), (2, "flops")),
                 _msg((1, 9), (2, scope + "x"))]
    ops = [
        (7, "%fusion.1 = bf16[8] fusion()", [_msg((1, 3), (5, scope)), _msg((1, 4), (3, 99))]),
        (8, "%fusion.2 = bf16[8] fusion()", [_msg((1, 3), (7, 9))]),
        (9, "%copy.3 = bf16[8] copy()", [_msg((1, 4), (3, 5))]),
    ]
    event_meta = [_msg((1, i), (2, name), *[(5, st) for st in stats]) for i, name, stats in ops]
    line = _msg((1, 1), (2, "XLA Ops"), (4, _msg((1, 7), (2, 5), (3, 10))))
    plane = _msg((1, 1), (2, TPU), (3, line),
                 *[(4, _msg((1, i), (2, m))) for (i, _, _), m in zip(ops, event_meta)],
                 *[(5, _msg((1, k), (2, m))) for k, m in zip((3, 4, 9), stat_meta)])
    host = _msg((1, 2), (2, "/host:CPU"), (4, _msg((1, 1), (2, event_meta[0]))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, plane), (1, host)))
    assert trace.op_scopes(str(path)) == {TPU: {"%fusion.1 = bf16[8] fusion()": scope,
                                                "%fusion.2 = bf16[8] fusion()": scope + "x"}}
