"""The comparison that decides ``correct``.

For a sample of the cells that the window published, drawn from the run's
seed, the published artifact is read back from the program's cache, with
the losses the timed path's training steps returned, and set beside the
plain reference (``plainref``) trained from the same cell seed at the
matmul precision the configuration states (``matmul_precision``).  The
numbers, each the worst over the sampled cells:

* ``loss_gap``: relative gap of the training loss at each of the first
  ``LOSS_STEPS`` steps: the forward pass at the seeded init, then after
  the first updates.
* ``param_gap``: for each weight or bias leaf, the norm of the difference
  of the published and the reference parameters, ``|P - R|``, over the
  larger of that leaf's and the median leaf's reference change from the
  seeded init.  Worst leaf.  Leaves whose reference first gradient is
  under a thousandth of the median leaf's are left out.
* ``change_gap``: on the same scale, the gap between the norms of the
  published and the reference change, ``| |P - P0| - |R - R0| |``.
* ``input_spikes``: largest difference of the traced input spike counts
  (layer 0).  They depend on the data and the trace key alone.
* ``spike_gap``: for each spiking layer after the input, the relative gap
  of the mean traced spike count per (step, sample).  Worst layer.
* ``accuracy_gap``: absolute gap of the test accuracy.

Training is chaotic at spike thresholds: a rounding difference flips a
spike, and after 150 steps two runs that round differently are different
members of one family.  So a reference that rounds otherwise than the
program (another matmul precision, or a conv summed in another order)
parts from it in every number, and one that rounds alike agrees bit for
bit.

A number is compared where the configuration file gives it a limit
(``limits``); ``calibrate.py`` reports them all, and PERF.md gives the
readings each limit was set from.  The published ``accuracy`` is reported
beside them.
"""
from __future__ import annotations

import numpy as np

import plainref

GRAD_FLOOR = 1e-3
LOSS_STEPS = 3


def _leaves(params) -> dict:
    return {f"l{i}.{k}": np.asarray(v, np.float64)
            for i, layer in enumerate(params) for k, v in sorted(layer.items())}


def loss_gaps(published: dict, ref: dict, steps: int) -> list[float]:
    """|program - reference| / |reference| of the loss at each of the first
    ``steps`` training steps."""
    lp = np.asarray(published["losses"][:steps], np.float64)
    lr = np.asarray(ref["losses"][:steps], np.float64)
    if len(lp) < steps or len(lr) < steps:
        raise ValueError(f"fewer than {steps} training losses to compare")
    return list(np.abs(lp - lr) / np.abs(lr))


def readings(published: dict, ref: dict) -> dict:
    """Every candidate number for one cell: ``published`` has ``params``,
    ``counts`` and ``accuracy`` as the program published them and
    ``losses``, its training steps' losses; ``ref`` is
    ``plainref.train_cell``'s result."""
    p, r = _leaves(published["params"]), _leaves(ref["params"])
    r0, g1 = _leaves(ref["params0"]), _leaves(ref["first_grad"])
    gnorm = {k: np.linalg.norm(v) for k, v in g1.items()}
    live = [k for k in r
            if gnorm[k] >= GRAD_FLOOR * np.median(list(gnorm.values()))]
    d_ref = {k: np.linalg.norm(r[k] - r0[k]) for k in live}
    d_pub = {k: np.linalg.norm(p[k] - r0[k]) for k in live}
    diff = {k: np.linalg.norm(p[k] - r[k]) for k in live}
    med = float(np.median(list(d_ref.values())))
    scale = {k: max(d_ref[k], med) for k in live}
    pc, rc = published["counts"], ref["counts"]
    spike = [abs(float(np.mean(a)) - float(np.mean(b)))
             / max(float(np.mean(b)), 1e-9) for a, b in zip(pc[1:], rc[1:])]
    return {
        "loss_gap": float(max(loss_gaps(published, ref, LOSS_STEPS))),
        "input_spikes": float(np.max(np.abs(np.asarray(pc[0], np.float64)
                                            - rc[0]))),
        "change_gap": max(abs(d_pub[k] - d_ref[k]) / scale[k] for k in live),
        "param_gap": max(diff[k] / scale[k] for k in live),
        "spike_gap": max(spike) if spike else 0.0,
        "accuracy_gap": abs(float(published["accuracy"])
                            - float(ref["accuracy"])),
        "accuracy": float(published["accuracy"]),
        "leaves_left_out": len(r) - len(live),
    }


def worst(per_cell: list[dict]) -> dict:
    return {k: max(c[k] for c in per_cell) for k in per_cell[0]}


def read_back(cache, workload, assignment, seed: int,
              losses: dict) -> dict:
    art = cache.resolve(workload, assignment, seed=seed, quant_bits=())
    if not art.cache_hit:
        raise RuntimeError(f"cell seed {seed} was not published")
    return {"params": art.params, "counts": art.counts,
            "accuracy": art.accuracy, "losses": losses.get(seed, [])}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that has a limit, beside it; correct when none is over
    its limit (an exact number's limit is 0)."""
    shown = {k: {"value": numbers[k], "limit": limits[k]}
             for k in sorted(limits)}
    ok = all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown


def check_cells(cache, workload, assignment, config: dict,
                seeds: list[int], losses: dict) -> dict:
    if not seeds:
        return {"correct": False, "numbers": {}}
    per_cell = []
    for seed in seeds:
        published = read_back(cache, workload, assignment, seed, losses)
        ref = plainref.train_cell(
            config["workload"], config["num_steps"], config["population"],
            seed, precision=config.get("matmul_precision", "default"))
        per_cell.append(readings(published, ref))
    numbers = worst(per_cell)
    ok, shown = judge(numbers, config.get("limits", {}))
    return {"correct": ok and bool(shown), "numbers": shown}
