"""Dense work of a cell, counted from its shapes.

Every count is of the dense layer, whatever tiles a kernel skips or pads,
so each implementation is judged on the same work: 2*M*K*N operations for a
Dense layer of batch M, and the im2col product M*(KH*KW*Cin)*Cout times two
for a conv, with M = batch * OH * OW.  A training step runs the forward
product and two backward ones (dW and dS) of the same size, so it counts
three times the forward.
"""
from __future__ import annotations

import math


def topology(wl: dict, population: float) -> list[tuple]:
    """Hidden layers scaled by the population multiplier, then the
    population-coded classifier."""
    layers = []
    for spec in wl["layers"]:
        if spec[0] in ("dense", "conv"):
            n = max(1, int(round(spec[1] * population)))
            layers.append((spec[0], n) + tuple(spec[2:]))
        else:
            layers.append(tuple(spec))
    layers.append(("dense", wl["num_classes"] * wl["pcr"]))
    return layers


def layer_gemms(wl: dict, population: float, batch: int) -> list[tuple]:
    """(M, K, N) of each weighted layer's product at this batch."""
    shape, out = tuple(wl["input_shape"]), []
    for layer in topology(wl, population):
        if layer[0] == "dense":
            out.append((batch, math.prod(shape), layer[1]))
            shape = (layer[1],)
        elif layer[0] == "conv":
            _, f, k, stride, padding = layer
            h, w, c = shape
            if padding == "SAME":
                oh, ow = -(-h // stride), -(-w // stride)
            else:
                oh, ow = (h - k) // stride + 1, (w - k) // stride + 1
            out.append((batch * oh * ow, k * k * c, f))
            shape = (oh, ow, f)
        else:
            h, w, c = shape
            shape = (h // layer[1], w // layer[1], c)
    return out


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def train_step_flops(wl: dict, num_steps: int, population: float) -> int:
    """Dense operations of one training step of one cell: forward and the
    two backward products of every layer, at every time step."""
    fwd = sum(gemm_flops(*g)
              for g in layer_gemms(wl, population, wl["batch_size"]))
    return 3 * fwd * num_steps
