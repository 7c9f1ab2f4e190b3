"""Mean seconds of a request's commit on the engine thread: the program's
``commit`` span around ``CacheHierarchy.commit`` and ``release``
(``RequestRecord.spans``: eviction for the new blocks and the write-behind
submit, backpressure included), over the window's requests.  None where the
program records no such span.

The same reading as ``hierarchy.commit_s``, in the cells whose end-to-end
TTFT is the tail (``ttft_p90_s``).
"""


def read(w):
    got = [s["commit"] for s in (getattr(r, "spans", None) for r in w.records)
           if s and "commit" in s]
    return sum(got) / len(got) if got else None
