"""Seconds the engine thread stood blocked on the full write-behind queue
(``CommitQueueStats.stall_s`` over the window), per request completed."""


def read(w):
    if not w.records or not w.counters_before:
        return None
    return w.delta("repro_commit_queue_stall_s") / len(w.records)
