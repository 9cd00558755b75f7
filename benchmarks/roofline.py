"""The least time one chip could take for one train step of a
configuration: the operations and bytes the stated algorithm needs,
computed from the configuration's shapes, against the chip's peaks.

Every term says what it counts. The floor counts only what ANY
implementation of the stated configuration (dense Adam, float32
parameters, bfloat16 moments, bfloat16 matmuls) has to do, so a reading
of `train_step_roofline` cannot pass 100%: terms that a better
kernel could avoid are listed with `in_floor: false` and left out.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

HOME = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device that is not in the table is
    an error, never a default."""
    with open(os.path.join(HOME, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json (have: {sorted(table)})")
    return table[device_kind]


def num_params(config: Dict) -> int:
    d = config["code_dim"]
    return (config["token_rows"] * config["token_dim"]
            + config["path_rows"] * config["path_dim"]
            + config["target_rows"] * d + d * d + d)


def train_step_terms(config: Dict, rows: int, valid_contexts: float
                     ) -> List[Dict]:
    """Operations and bytes of one dense-Adam train step on one chip
    that holds `rows` rows of the batch; `valid_contexts` is the mean
    number of real (not padded) contexts in a row of the traffic."""
    m, d, v = config["max_contexts"], config["code_dim"], config["target_rows"]
    td, pd = config["token_dim"], config["path_dim"]
    row_bytes = (2 * td + pd) * 4           # three float32 rows a context
    n = num_params(config)
    return [
        {"term": "transform matmul, forward + two backward products "
                 "(2 flops a multiply-add, every context slot of the "
                 "padded batch: the shapes are static)",
         "flops": 3 * 2 * rows * m * d * d, "bytes": 0, "in_floor": True},
        {"term": "attention scores and weighted sum, forward + backward",
         "flops": 2 * 3 * 2 * rows * m * d, "bytes": 0, "in_floor": True},
        {"term": "logits matmul code @ TARGET^T, forward + two backward "
                 "products",
         "flops": 3 * 2 * rows * d * v, "bytes": 0, "in_floor": True},
        {"term": "rows gathered: three float32 table rows read for each "
                 "REAL context (padding needs none)",
         "flops": 0, "bytes": rows * valid_contexts * row_bytes,
         "in_floor": True},
        {"term": "rows scattered: the same rows' gradients written once",
         "flops": 0, "bytes": rows * valid_contexts * row_bytes,
         "in_floor": True},
        {"term": "dense Adam, 20 B a parameter: float32 parameter read "
                 "and written (8), float32 gradient read (4), bfloat16 "
                 "first and second moment read and written (8)",
         "flops": 0, "bytes": 20 * n, "in_floor": True},
        {"term": "logits chain: float32 (rows, targets) logits written "
                 "by the forward and read by the backward; a blockwise "
                 "cross-entropy needs none, so it is NOT in the floor",
         "flops": 0, "bytes": 2 * rows * v * 4, "in_floor": False},
    ]


def train_step_floor(config: Dict, rows: int, valid_contexts: float,
                     device_kind: str) -> Dict:
    """The floor in seconds and which bound it is."""
    peaks = peaks_for(device_kind)
    terms = [t for t in train_step_terms(config, rows, valid_contexts)
             if t["in_floor"]]
    flops = sum(t["flops"] for t in terms)
    nbytes = sum(t["bytes"] for t in terms)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "flops": flops,
            "bytes": nbytes, "seconds_by_flops": by_flops,
            "seconds_by_bytes": by_bytes,
            "bound": "bytes" if by_bytes >= by_flops else "flops"}
