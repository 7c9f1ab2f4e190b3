"""Mean I/O charged to a request by the engine (``RequestRecord.io_s``:
the wait on its store fetch plus the install), over the window's requests."""


def read(w):
    recs = w.records
    return sum(r.io_s for r in recs) / len(recs) if recs else None
