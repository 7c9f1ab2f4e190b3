"""Mean seconds of the copy of the prompt's float16 block rows from the
device to the host: the program's ``device_to_host`` span in
``RealModel.__call__`` (``RequestRecord.spans``), over the window's requests
that ran a prefill.  None where the program records no such span.
"""


def read(w):
    got = [s["device_to_host"] for s in (getattr(r, "spans", None) for r in w.records)
           if s and "device_to_host" in s]
    return sum(got) / len(got) if got else None
