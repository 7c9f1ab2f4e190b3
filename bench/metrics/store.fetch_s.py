"""Mean seconds of a fetch that reached the store: the promotion time the
hierarchy adds to ``CacheStats.promote_s`` (fetch plus install), over the
window's fulfills that took blocks from the store."""


def read(w):
    got = [io_s for _, io_s, disk_tokens in w.fulfills if disk_tokens > 0]
    return sum(got) / len(got) if got else None
