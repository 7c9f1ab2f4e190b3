"""Mean seconds of a fetch that reached the store: the promotion time the
hierarchy adds to ``CacheStats.promote_s`` (fetch plus install), over the
window's fulfills that took blocks from the store.

The same reading as ``store.fetch_s``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    got = [io_s for _, io_s, disk_tokens in w.fulfills if disk_tokens > 0]
    return sum(got) / len(got) if got else None
