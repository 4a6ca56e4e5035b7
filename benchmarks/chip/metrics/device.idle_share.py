"""Share of the traced slice in which no operation ran on the chip, in
percent: 1 - busy / slice, busy being the union of the ``XLA Ops``
intervals of each chip, averaged over the cell's chips."""


def read(record):
    trace = record["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
