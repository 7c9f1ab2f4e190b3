"""The prefill's share of the chip's bf16 peak, in percent: the operations
the window's prefills require (``bench/flops.py``: the uncached suffix,
attention over the whole context, the head on the last position) over the
prefill program's device time in the trace."""


def read(w):
    red = w.reduction
    if red is None or not red.program_s.get("prefill") or not w.required_flops:
        return None
    return 100.0 * w.required_flops / (red.program_s["prefill"] * w.peaks["bf16_flops_per_s"])
