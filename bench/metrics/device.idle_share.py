"""Share of the traced window in which no operation ran on the chip."""


def read(w):
    red = w.reduction
    return red.idle_share if red is not None and red.devices else None
