"""Device milliseconds per execution of the jitted training step
(``jit_train_step`` solo, ``jit_stack_step`` stacked) in the traced slice,
from the ``XLA Modules`` line of the first chip."""

PROGRAMS = ("jit_train_step", "jit_stack_step")


def read(record):
    trace = record["trace"]
    if not trace:
        return None
    runs = [trace["programs"][p] for p in PROGRAMS if p in trace["programs"]]
    count = sum(r[1] for r in runs)
    if not count:
        return None
    return sum(r[0] for r in runs) / count * 1e3
