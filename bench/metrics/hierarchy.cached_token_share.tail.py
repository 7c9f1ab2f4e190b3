"""Share of requested prompt tokens the hierarchy served from any tier
(``CacheStats`` tokens hit in device, host and store, over tokens
requested), counted over the window.

The same reading as ``hierarchy.cached_token_share``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    req = w.delta("repro_cache_tokens_requested")
    if not req:
        return None
    hit = sum(w.delta(f"repro_cache_tokens_hit_{t}") for t in ("device", "host", "disk"))
    return hit / req
