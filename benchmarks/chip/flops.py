"""Operations and bytes of the conv1d cost model, from its shapes.

These are what the algorithm needs, not what a compiler emitted:
multiply-adds count two operations; bias adds, ReLUs, the pad mask and
the max-pool one per element. Bytes are the least a forward must move
through device memory: the ids, the embedding rows it gathers, every
weight once and the predictions. A training step counts the forward's
matrix products three times (forward, and the two products of the
backward) and moves every weight, its gradient and both Adam moments.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device the table does not name is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _layers(cfg: dict):
    c_in = cfg["embed_dim"]
    for fs, c_out in zip(cfg["conv_filters"], cfg["conv_channels"]):
        yield fs, c_in, c_out
        c_in = c_out


def matmul_flops_per_position(cfg: dict) -> int:
    return sum(2 * fs * ci * co for fs, ci, co in _layers(cfg))


def matmul_flops_per_row(cfg: dict) -> int:
    dims = [cfg["conv_channels"][-1], *cfg["fc_dims"]]
    fc = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    return fc + 2 * dims[-1] * len(cfg["heads"])


def n_weights(cfg: dict) -> int:
    conv = sum(fs * ci * co + co for fs, ci, co in _layers(cfg))
    dims = [cfg["conv_channels"][-1], *cfg["fc_dims"]]
    fc = sum(a * b + b for a, b in zip(dims, dims[1:]))
    return conv + fc + (dims[-1] + 1) * len(cfg["heads"])


def forward(cfg: dict, batch: int, width: int) -> Tuple[float, float]:
    """(operations, bytes) of one forward over (batch, width) ids."""
    pos = batch * width
    elem = cfg["embed_dim"] + sum(2 * co for _, _, co in _layers(cfg)) \
        + cfg["conv_channels"][-1]
    row_elem = sum(2 * d for d in cfg["fc_dims"]) + len(cfg["heads"])
    ops = pos * (matmul_flops_per_position(cfg) + elem) \
        + batch * (matmul_flops_per_row(cfg) + row_elem)
    nbytes = 4 * (pos + pos * cfg["embed_dim"] + n_weights(cfg)
                  + batch * len(cfg["heads"]))
    return float(ops), float(nbytes)


def train_step(cfg: dict, batch: int, width: int) -> Tuple[float, float]:
    """(operations, bytes) of one training step's matrix products and
    its least memory traffic."""
    pos = batch * width
    ops = 3 * (pos * matmul_flops_per_position(cfg)
               + batch * matmul_flops_per_row(cfg))
    weights = n_weights(cfg) + cfg["vocab_size"] * cfg["embed_dim"]
    nbytes = 4 * (pos + 2 * pos * cfg["embed_dim"] + 7 * weights)
    return float(ops), float(nbytes)


def least_time(ops: float, nbytes: float, device_kind: str
               ) -> Tuple[float, str]:
    """Least seconds the chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    t_c, t_m = ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
