"""Mean seconds a request waited before its own service began
(``RequestRecord.queue_s``: from ``ServingEngine.submit`` to the start of its
service: the engine's deque, and being staged one step ahead by the
prefetch pipeline), over the window's requests.  None where the program
stamps no such time.
"""


def read(w):
    got = [r.queue_s for r in w.records if getattr(r, "queue_s", None) is not None]
    return sum(got) / len(got) if got else None
