"""Process start to the window's start: imports, device start, data from
the seed, compile or cache load, warm-up."""


def read(r):
    return r.setup_s
