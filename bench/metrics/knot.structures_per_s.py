"""Structures of the batches that completed in the window, over the
window, on the host clock: the campaign's throughput, which the host-bound
screen sets. The driver takes it in every run."""


def read(r):
    return r.get("structures_per_s")
