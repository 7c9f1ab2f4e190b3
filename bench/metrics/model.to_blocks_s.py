"""Mean seconds the host takes to turn the copied K/V cache into float16
blocks (cast, concatenate, transpose, slice): the program's ``to_blocks``
span in ``RealModel.__call__`` (``RequestRecord.spans``), over the window's
requests that ran a prefill.  None where the program records no such
span.
"""


def read(w):
    got = [s["to_blocks"] for s in (getattr(r, "spans", None) for r in w.records)
           if s and "to_blocks" in s]
    return sum(got) / len(got) if got else None
