"""Dense training operations completed in the window over what the chips'
peak could do in it, in percent: cells x steps x dense forward and backward
operations of one cell-step (``work.train_step_flops``), over seconds x
chips x peak FLOP/s (``peaks.json``).  Cells and seconds are the window's
outside the traced unit, which the profiler slows."""


def read(record):
    if not record["untraced_cells"] or not record["peak"]:
        return None
    done = (record["untraced_cells"] * record["train_steps"]
            * record["step_flops"])
    capacity = (record["untraced_s"] * record["chips"]
                * record["peak"]["flops_per_s"])
    return 100.0 * done / capacity
