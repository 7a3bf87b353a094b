"""Wall time of the window, from its start to the completion of its last
selection, over the selections completed."""


def read(r):
    w = r.window
    return (w.end - w.start) / max(r.selections, 1)
