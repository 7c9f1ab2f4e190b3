"""Share of requested prompt tokens the hierarchy took from the store
(``CacheStats.tokens_hit_disk`` over tokens requested), over the window.

The same reading as ``hierarchy.store_token_share``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    req = w.delta("repro_cache_tokens_requested")
    return w.delta("repro_cache_tokens_hit_disk") / req if req else None
