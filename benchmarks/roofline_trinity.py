"""The least time one chip could take for the two attentions and for the
WHOLE scoring step of the window / full attention expert model, from the
configuration's shapes and the step's own counts, against the chip's
published peaks (`peaks.json`, through `roofline.peaks_for`).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing): no masked
pair, no page a row does not hold, no ring row outside the window. So a
share cannot pass 100 %.

The counts of one row with `held` cached tokens and `q` real question
tokens, a layer (`row_counts`): a FULL layer sees `held + q` keys and
`q held + q (q + 1) / 2` (query, key) pairs; a WINDOW layer of `W` sees
`min(held, W - 1) + q` keys and `sum_i min(held + i + 1, W)` pairs.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.roofline_glm import moe_gated_experts_terms
from benchmarks.roofline_lm import _floor


def row_counts(held: int, q: int, window: int) -> Dict[str, int]:
    """One row's visible keys and (query, visible key) pairs, a layer of
    each kind."""
    reach = [min(held + i + 1, window) for i in range(q)]
    return {"full_keys": held + q,
            "full_pairs": q * held + q * (q + 1) // 2,
            "window_keys": min(held, window - 1) + q,
            "window_pairs": sum(reach)}


def attend_terms(config: Dict, queries: float, keys: float, pairs: float
                 ) -> List[Dict]:
    """One layer's attention of one step, either kind: `queries` real
    question tokens over all rows, `keys` tokens the rows may see summed
    over the rows (cached and own), `pairs` (query, visible key) pairs.
    bfloat16 operands."""
    hq, hkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    return [
        {"term": "scores and weighted sums, 2 flops a multiply-add: 4 x "
                 "head_dim flops a (query, visible key, query head)",
         "flops": 4 * d * hq * pairs, "bytes": 0},
        {"term": "keys and values of every visible token, read once a "
                 "row and layer",
         "flops": 0, "bytes": keys * 2 * hkv * d * 2},
        {"term": "queries in, attention out, once a query (bfloat16)",
         "flops": 0, "bytes": queries * hq * d * 2 * 2},
    ]


def attention_matrices(config: Dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + h * q       # q, k, v, o, the gate


def score_step_terms(config: Dict, step_rows: float, tokens: float,
                     window: Dict[str, float], full: Dict[str, float],
                     assignments: float, experts_hit: float) -> List[Dict]:
    """The WHOLE scoring step: `step_rows` real rows (each answered by
    the head), `tokens` real tokens; `window` and `full` the step's
    `{"keys", "pairs"}` a layer of the kind; `assignments` and
    `experts_hit` a (step, expert layer). Every layer held, the
    embedding's rows and the head; of the routed experts only those HIT
    are read."""
    h, v = config["hidden_size"], config["vocab_rows"]
    kinds = [k for k in config["layer_types"][:config["layers"]]]
    n_window = kinds.count("sliding_attention")
    n_full = len(kinds) - n_window
    dense = min(config["num_dense_layers"], config["layers"])
    expert = config["layers"] - dense
    shared = 3 * h * config["num_shared_experts"] \
        * config["moe_intermediate_size"]
    every = (config["layers"] * attention_matrices(config)
             + dense * 3 * h * config["intermediate_size"]
             + expert * (h * config["num_experts"] + shared))
    out = [
        {"term": "the matrices every token passes (attention with its "
                 "gate, the dense MLPs, routers, shared experts), "
                 "bfloat16, read once a step",
         "flops": 0, "bytes": every * 2},
        {"term": "those matrices against every real token, 2 flops a "
                 "multiply-add",
         "flops": 2 * every * tokens, "bytes": 0},
        {"term": "the head: every row held against each real row's last "
                 "state, its matrix (bfloat16) read once",
         "flops": 2 * v * h * step_rows, "bytes": v * h * 2},
        {"term": "the embedding's rows of the real tokens",
         "flops": 0, "bytes": tokens * h * 2},
    ]
    for count, name, counts in ((n_window, "window", window),
                                (n_full, "full", full)):
        for term in attend_terms(config, tokens, counts["keys"],
                                 counts["pairs"]):
            out.append({"term": f"{count} {name} layers x " + term["term"],
                        "flops": count * term["flops"],
                        "bytes": count * term["bytes"]})
    for term in moe_gated_experts_terms(config, assignments, experts_hit):
        out.append({"term": f"{expert} expert layers x " + term["term"],
                    "flops": expert * term["flops"],
                    "bytes": expert * term["bytes"]})
    return out


def attend_floor(config: Dict, queries: float, keys: float, pairs: float,
                 device_kind: str) -> Dict:
    return _floor(attend_terms(config, queries, keys, pairs), device_kind)


def score_step_floor(config: Dict, step_rows: float, tokens: float,
                     window: Dict[str, float], full: Dict[str, float],
                     assignments: float, experts_hit: float,
                     device_kind: str) -> Dict:
    return _floor(score_step_terms(config, step_rows, tokens, window, full,
                                   assignments, experts_hit), device_kind)
