"""Mean I/O charged to a request by the engine (``RequestRecord.io_s``:
the wait on its store fetch plus the install), over the window's requests.

The same reading as ``engine.io_charged_s``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    recs = w.records
    return sum(r.io_s for r in recs) / len(recs) if recs else None
