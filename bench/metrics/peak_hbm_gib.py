"""Peak device memory of the fullest chip, read after the window and
before the comparison: buffers in use plus the region the runtime
reserves for executables' temporaries."""


def read(r):
    return r.peak_bytes / 2 ** 30
