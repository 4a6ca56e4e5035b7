"""Seconds of XLA compilation inside the window per cell completed: the
``backend_compile_duration`` events of ``jax.monitoring`` (a program served
by the persistent compile cache counts its load)."""


def read(record):
    if not record["cells"]:
        return None
    return record["compile_s"] / record["cells"]
