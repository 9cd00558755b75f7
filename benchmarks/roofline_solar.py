"""The least time one chip could take for the delta rule of one K layer
and for the WHOLE extending step of the delta-rule / grouped-query expert
model, from the configuration's shapes and the step's own counts,
against the chip's published peaks (`peaks.json`, through
`roofline.peaks_for`). The G layer's attention and the held experts have
their floors already (`roofline_trinity.attend_terms`,
`roofline_glm.moe_gated_experts_terms`: the same layers at other
widths).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing), so a share
cannot pass 100 %. The delta rule's is the RECURRENCE's own arithmetic,
a token and head: the decay of the state (dk dv), the read along the key
(2 dk dv), the rank-one write (2 dk dv) and the read along the query (2
dk dv); a chunked form multiplies more (its solve, its (token, token)
matrices) and those are not counted. Its bytes: a row's state read once
and written once (float32), the conv's carried inputs both ways, and q,
k, v, the decays (float32, as the op is handed them) and the write
strengths in, the outputs out.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.roofline_glm import moe_gated_experts_terms
from benchmarks.roofline_lm import _floor
from benchmarks.roofline_trinity import attend_terms


def turn_counts(held: int, q: int) -> Dict[str, int]:
    """One row's visible keys and (query, visible key) pairs in the G
    layer: `held` cached tokens and `q` real tokens of the turn."""
    return {"keys": held + q, "pairs": q * held + q * (q + 1) // 2}


def delta_rule_terms(config: Dict, rows: float, tokens: float) -> List[Dict]:
    """One K layer's delta rule of one step: `rows` real rows (each
    reads and writes one state), `tokens` real tokens over all rows."""
    lin = config["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    return [
        {"term": "the recurrence, a token and head: decay dk dv, the read "
                 "along the key, the rank-one write and the read along "
                 "the query 2 dk dv each",
         "flops": 7 * n * d * d * tokens, "bytes": 0},
        {"term": "a row's state of every head, float32, read once and "
                 "written once",
         "flops": 0, "bytes": rows * n * d * d * 4 * 2},
        {"term": "the conv's carried inputs of x_q | x_k | x_v, bfloat16, "
                 "read once and written once a row",
         "flops": 0, "bytes": rows * (taps - 1) * 3 * n * d * 2 * 2},
        {"term": "q, k, v and the decays in, the outputs out, float32, "
                 "once a token; the write strengths a head",
         "flops": 0, "bytes": tokens * (5 * n * d + n) * 4},
    ]


def mixer_matrices(config: Dict) -> Dict[str, int]:
    """Elements of the matrices every token of a layer passes, by the
    layer's mixer."""
    h, d = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d
    lin = config["linear_attn_config"]
    n, ld = lin["num_heads"], lin["head_dim"]
    lq = n * ld
    return {"G": h * q + 2 * h * kv + q * h + h * q,    # q, k, v, o, gate
            "K": (3 * h * lq + lq * h                   # q, k, v, o
                  + 2 * (h * ld + ld * lq)              # the two low-rank pairs
                  + h * n)}                             # the write strength


def extend_step_terms(config: Dict, step_rows: float, tokens: float,
                      keys: float, pairs: float, assignments: float,
                      experts_hit: float) -> List[Dict]:
    """The WHOLE extending step: `step_rows` real rows (each answered by
    the head, each reading and writing a state a K layer), `tokens` real
    tokens; `keys` and `pairs` the step's visible keys and (query,
    visible key) pairs in a G layer; `assignments` and `experts_hit` a
    (step, expert layer). Every layer held, the embedding's rows and the
    head; of the routed experts only those HIT are read; the new tokens'
    keys and values written once."""
    h, v = config["hidden_size"], config["vocab_rows"]
    kinds = ["G" if i in config["gqa_layers"] else "K"
             for i in range(config["layers"])]
    matrices = mixer_matrices(config)
    shared = 3 * h * config["n_shared_experts"] \
        * config["moe_intermediate_size"]
    every = (sum(matrices[k] for k in kinds)
             + len(kinds) * (h * config["n_routed_experts"] + shared))
    kv = 2 * config["num_key_value_heads"] * config["head_dim"]
    out = [
        {"term": "the matrices every token passes (the mixers with their "
                 "gates, routers, shared experts), bfloat16, read once a "
                 "step",
         "flops": 0, "bytes": every * 2},
        {"term": "those matrices against every real token, 2 flops a "
                 "multiply-add",
         "flops": 2 * every * tokens, "bytes": 0},
        {"term": "the head: every row held against each real row's last "
                 "state, its matrix (bfloat16) read once",
         "flops": 2 * v * h * step_rows, "bytes": v * h * 2},
        {"term": "the embedding's rows of the real tokens",
         "flops": 0, "bytes": tokens * h * 2},
        {"term": f"{kinds.count('G')} G layers x the new tokens' keys and "
                 f"values written to their pages, bfloat16",
         "flops": 0, "bytes": kinds.count("G") * tokens * kv * 2},
    ]
    for count, name, terms in (
            (kinds.count("G"), "G", attend_terms(config, tokens, keys,
                                                 pairs)),
            (kinds.count("K"), "K", delta_rule_terms(config, step_rows,
                                                     tokens)),
            (len(kinds), "expert", moe_gated_experts_terms(
                config, assignments, experts_hit))):
        for term in terms:
            out.append({"term": f"{count} {name} layers x " + term["term"],
                        "flops": count * term["flops"],
                        "bytes": count * term["bytes"]})
    return out


def delta_rule_floor(config: Dict, rows: float, tokens: float,
                     device_kind: str) -> Dict:
    return _floor(delta_rule_terms(config, rows, tokens), device_kind)


def attend_floor(config: Dict, queries: float, keys: float, pairs: float,
                 device_kind: str) -> Dict:
    return _floor(attend_terms(config, queries, keys, pairs), device_kind)


def extend_step_floor(config: Dict, step_rows: float, tokens: float,
                      keys: float, pairs: float, assignments: float,
                      experts_hit: float, device_kind: str) -> Dict:
    return _floor(extend_step_terms(config, step_rows, tokens, keys, pairs,
                                    assignments, experts_hit), device_kind)
