"""The least time one chip could take for the two new kernels of the
grouped-query / selected-key model's scoring step, from the
configuration's shapes and the step's own counts, against the chip's
published peaks (`peaks.json`, through `roofline.peaks_for`).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing), whatever
implements it: no score buffer, no counting passes, no masked pairs. So
a share cannot pass 100 %.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.roofline_lm import _floor


def index_select_terms(config: Dict, queries: float, keys: float,
                       pairs: float) -> List[Dict]:
    """One layer's indexer of one step: `queries` real question tokens
    over all rows, `keys` tokens a row may see summed over the rows
    (cached and own), `pairs` (query, visible key) pairs. Scoring every
    visible key is the selection's work; choosing among the scores needs
    no further bytes. bfloat16 operands."""
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return [
        {"term": "index scores: heads x head_dim multiply-adds a (query, "
                 "visible key) pair, 2 flops each",
         "flops": 2 * hi * di * pairs, "bytes": 0},
        {"term": "the index key of every visible token, read once a row "
                 "and layer",
         "flops": 0, "bytes": keys * di * 2},
        {"term": "index queries (bfloat16) and head weights (float32) in, "
                 "once a query",
         "flops": 0, "bytes": queries * hi * (di * 2 + 4)},
    ]


def sparse_attend_terms(config: Dict, queries: float, keys: float,
                        selected_pairs: float) -> List[Dict]:
    """One layer's attention over the kept keys of one step:
    `selected_pairs` (query, kept key) pairs. The keys and values can be
    read in two forms; the floor takes the one with FEWER bytes at the
    step's counts, so the share means the same whichever form the
    program runs."""
    hq, hkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    token = 2 * hkv * d * 2             # keys and values, bfloat16
    gathered, masked = selected_pairs * token, keys * token
    return [
        {"term": "scores and weighted sums over the KEPT pairs alone, 2 "
                 "flops a multiply-add, every query head",
         "flops": 2 * 2 * hq * d * selected_pairs, "bytes": 0},
        {"term": "keys and values: the cheaper of GATHERED (each kept "
                 "token's read once a query) and MASKED (each visible "
                 "token's read once a row)",
         "flops": 0, "bytes": min(gathered, masked),
         "gathered_bytes": gathered, "masked_bytes": masked},
        {"term": "queries in, attention out, once a query (bfloat16)",
         "flops": 0, "bytes": queries * hq * d * 2 * 2},
    ]


def index_select_floor(config: Dict, queries: float, keys: float,
                       pairs: float, device_kind: str) -> Dict:
    return _floor(index_select_terms(config, queries, keys, pairs),
                  device_kind)


def sparse_attend_floor(config: Dict, queries: float, keys: float,
                        selected_pairs: float, device_kind: str) -> Dict:
    return _floor(sparse_attend_terms(config, queries, keys,
                                      selected_pairs), device_kind)
