"""The prefill's share of the chip's bf16 peak, in percent: the operations
the window's prefills require (``bench/flops.py``: the uncached suffix,
attention over the whole context, the head on the last position) over the
prefill program's device time in the trace.

The same reading as ``model.prefill_mfu``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    red = w.reduction
    if red is None or not red.program_s.get("prefill") or not w.required_flops:
        return None
    return 100.0 * w.required_flops / (red.program_s["prefill"] * w.peaks["bf16_flops_per_s"])
