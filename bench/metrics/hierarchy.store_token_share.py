"""Share of requested prompt tokens the hierarchy took from the store
(``CacheStats.tokens_hit_disk`` over tokens requested), over the window."""


def read(w):
    req = w.delta("repro_cache_tokens_requested")
    return w.delta("repro_cache_tokens_hit_disk") / req if req else None
