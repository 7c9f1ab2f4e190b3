"""The 50th percentile of the client's TTFT over the requests due in the
window, as the end-to-end ``ttft_p50_s`` reads it: for a cell in which that
end-to-end metric runs too unsteadily to be held to a bound."""

from bench import stats


def read(w):
    return stats.percentile(w.ttft, 50) if w.ttft else None
