"""Bytes and operations the algorithms need, computed from shapes. The
yardstick's arithmetic lives here, not in the program (the sg-ns model is a
copy of ``bench.py::_sg_ns_roofline``'s byte count)."""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a device that is not in the table is an
    error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"in benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def sgns_adagrad_bytes_per_pair(dim: int, negatives: int,
                                param_bytes: int = 4) -> int:
    """HBM bytes one skip-gram pair with K negatives needs under AdaGrad.

    Rows touched: the center's ``w_in`` row and 1 + K ``w_out`` rows, 2 + K
    in all. Each is read and read-modify-written as a parameter row
    (3 x param_bytes per element) and as a float32 accumulator row
    (3 x 4 bytes). D=128, K=5, float32: 7 x 128 x 24 = 21,504 bytes."""
    return (2 + negatives) * dim * (3 * param_bytes + 3 * 4)


def sgns_adagrad_flops_per_pair(dim: int, negatives: int) -> int:
    """Forward dots 2(1+K)D, gradients 4(1+K)D, AdaGrad ~4(2+K)D."""
    return 6 * (1 + negatives) * dim + 4 * (2 + negatives) * dim


def gather_bytes_per_row(dim: int, param_bytes: int = 4) -> int:
    """A served row is read from the table and written to the reply buffer."""
    return 2 * dim * param_bytes
