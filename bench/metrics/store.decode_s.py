"""Mean seconds of decoding blocks read from the store (int8+zlib back to
float16): the program's ``decode`` span in ``KVBlockStore.get_batch``, taken
on an I/O worker under the request's trace (``RequestRecord.spans``), over
the window's requests whose fetch reached the store.  A hedged fetch sums
the decode of both attempts.  None where the program records no such
span.
"""


def read(w):
    got = [s["decode"] for s in (getattr(r, "spans", None) for r in w.records)
           if s and "decode" in s]
    return sum(got) / len(got) if got else None
