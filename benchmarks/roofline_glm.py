"""The least time one chip could take for the two new kernels of the
latent-attention / gated-expert model's scoring step, from the
configuration's shapes and the step's own counts, against the chip's
published peaks (`peaks.json`, through `roofline.peaks_for`).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing). Latent
attention can be computed in two forms over the same weights; the floor
takes the form with FEWER operations at the step's counts, so the share
means the same whichever form the program runs and cannot pass 100 %.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.roofline_lm import _floor


def mla_attend_terms(config: Dict, queries: float, keys: float,
                     pairs: float) -> List[Dict]:
    """One layer's attention of one step: `queries` real question tokens
    over all rows, `keys` latents read (each row's cached tokens and its
    own), `pairs` (query, visible key) pairs. bfloat16 operands."""
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    absorbed = (2 * pairs * heads * (2 * rank + dr)
                + 2 * queries * heads * rank * (dn + dv))
    expanded = (2 * pairs * heads * (dn + dr + dv)
                + 2 * keys * rank * heads * (dn + dv))
    return [
        {"term": "scores and weighted sums, 2 flops a multiply-add: the "
                 "cheaper of the absorbed form (a pair costs 2 kv_rank + "
                 "d_rope a head, a query's two folds kv_rank (d_nope + "
                 "d_v) a head) and the expanded one (a pair d_nope + "
                 "d_rope + d_v a head, a key's expansion kv_rank (d_nope "
                 "+ d_v) a head)",
         "flops": min(absorbed, expanded), "bytes": 0,
         "absorbed_flops": absorbed, "expanded_flops": expanded},
        {"term": "the latent of every key, read once a row and layer",
         "flops": 0, "bytes": keys * (rank + dr) * 2},
        {"term": "W_kvb, read once", "flops": 0,
         "bytes": rank * heads * (dn + dv) * 2},
        {"term": "queries in, attention out, once a query (bfloat16)",
         "flops": 0, "bytes": queries * heads * (dn + dr + dv) * 2},
    ]


def moe_gated_experts_terms(config: Dict, assignments: float,
                            experts_hit: float) -> List[Dict]:
    """One expert layer's routed experts of one step: `assignments`
    (real token, chosen expert held) pairs, `experts_hit` held experts
    that got at least one token. bfloat16 weights."""
    h, w = config["hidden_size"], config["moe_intermediate_size"]
    return [
        {"term": "gate, up and down of every expert that got a token, "
                 "read once",
         "flops": 0, "bytes": experts_hit * 3 * h * w * 2},
        {"term": "the three grouped matmuls, 2 flops a multiply-add, of "
                 "the real assignments",
         "flops": 2 * 3 * assignments * h * w, "bytes": 0},
        {"term": "hidden rows in (bfloat16) and out (float32) of the "
                 "experts, once an assignment",
         "flops": 0, "bytes": assignments * h * (2 + 4)},
    ]


def mla_attend_floor(config: Dict, queries: float, keys: float,
                     pairs: float, device_kind: str) -> Dict:
    return _floor(mla_attend_terms(config, queries, keys, pairs),
                  device_kind)


def moe_gated_experts_floor(config: Dict, assignments: float,
                            experts_hit: float, device_kind: str) -> Dict:
    return _floor(moe_gated_experts_terms(config, assignments, experts_hit),
                  device_kind)
