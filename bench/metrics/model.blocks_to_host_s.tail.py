"""Mean seconds the prefill hook spends beyond the prefill it reports:
copying the cache to the host and slicing it into blocks.

The same reading as ``model.blocks_to_host_s``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    calls = w.hook_calls
    return sum(wall - prefill for _, wall, prefill, _, _ in calls) / len(calls) if calls else None
