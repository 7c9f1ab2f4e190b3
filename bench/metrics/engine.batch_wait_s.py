"""Mean seconds from the end of a request's own service to the return of the
``ServingEngine.step()`` that returned it (``RequestRecord.batch_wait_s``:
the rest of its batch, and maintenance), over the window's requests.  None
where the program stamps no such time.
"""


def read(w):
    got = [r.batch_wait_s for r in w.records if getattr(r, "batch_wait_s", None) is not None]
    return sum(got) / len(got) if got else None
