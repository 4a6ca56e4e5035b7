"""Device milliseconds per execution of the jitted trace dump
(``jit_spike_counts``: the spikes entering each spiking layer of the traced
samples, counted inside the time loop) in the traced slice, from the ``XLA
Modules`` line of the cell's chips."""

PROGRAM = "jit_spike_counts"


def read(record):
    trace = record["trace"]
    if not trace or PROGRAM not in trace["programs"]:
        return None
    seconds, count = trace["programs"][PROGRAM]
    return seconds / count * 1e3 if count else None
