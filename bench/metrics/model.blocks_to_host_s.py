"""Mean seconds the prefill hook spends beyond the prefill it reports:
copying the cache to the host and slicing it into blocks."""


def read(w):
    calls = w.hook_calls
    return sum(wall - prefill for _, wall, prefill, _, _ in calls) / len(calls) if calls else None
