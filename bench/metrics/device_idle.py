"""Device idle share: 1 - busy / window, where busy is the union of the
intervals in which an op ran on a chip, averaged over the cell's chips."""


def read(r):
    s = r.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
