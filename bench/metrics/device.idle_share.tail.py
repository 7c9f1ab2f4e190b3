"""Share of the traced window in which no operation ran on the chip.

The same reading as ``device.idle_share``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    red = w.reduction
    return red.idle_share if red is not None and red.devices else None
