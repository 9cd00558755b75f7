"""The least time one chip could take for the two new kernels of the
hybrid language model's scoring step, from the configuration's shapes
and the step's own counts, against the chip's published peaks
(`peaks.json`, through `roofline.peaks_for`).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing), so a share
cannot pass 100 %.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks import roofline


def moe_experts_terms(config: Dict, tokens: float, assignments: float,
                      experts_hit: float) -> List[Dict]:
    """One expert layer of one step: `tokens` real tokens, `assignments`
    (token, chosen expert) pairs whose expert is held here, `experts_hit`
    held experts that got at least one token. bfloat16 weights."""
    h, lat = config["hidden_size"], config["moe_latent_size"]
    w = config["moe_intermediate_size"]
    return [
        {"term": "latent projections down and up, 2 flops a multiply-add, "
                 "every real token; their weights read once",
         "flops": 2 * 2 * tokens * h * lat, "bytes": 2 * 2 * h * lat},
        {"term": "W1 and W2 of every expert that got a token, read once",
         "flops": 0, "bytes": experts_hit * 2 * lat * w * 2},
        {"term": "the two expert matmuls, 2 flops a multiply-add, of the "
                 "real assignments",
         "flops": 2 * 2 * assignments * lat * w, "bytes": 0},
        {"term": "latent rows in (bfloat16) and out (float32) of the "
                 "experts, once an assignment",
         "flops": 0, "bytes": assignments * lat * (2 + 4)},
    ]


def ssd_scan_terms(config: Dict, tokens: float) -> List[Dict]:
    """One Mamba layer's selective scan over `tokens` real tokens, in
    chunks of `chunk_size`: its matmul flops and its state traffic."""
    nh, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n, q = (config["n_groups"], config["ssm_state_size"],
               config["chunk_size"])
    chunks = tokens / q
    return [
        {"term": "C B^T inside a chunk, by group: chunk x chunk x state",
         "flops": 2 * tokens * q * n * g, "bytes": 0},
        {"term": "(C B^T * decay) @ (dt x) inside a chunk, by head",
         "flops": 2 * tokens * q * p * nh, "bytes": 0},
        {"term": "B^T (dt x): each chunk's state, by head",
         "flops": 2 * tokens * n * p * nh, "bytes": 0},
        {"term": "C S: what the entering state adds, by head",
         "flops": 2 * tokens * n * p * nh, "bytes": 0},
        {"term": "state traffic: a float32 (head_dim x state) state a "
                 "head and chunk written once and read once",
         "flops": 0, "bytes": chunks * nh * p * n * 4 * 2},
        {"term": "x, B, C (bfloat16) and dt (float32) read, y (float32) "
                 "written, once a token",
         "flops": 0,
         "bytes": tokens * (nh * p * 2 + 2 * g * n * 2 + nh * 4
                            + nh * p * 4)},
    ]


def _floor(terms: List[Dict], device_kind: str) -> Dict:
    peaks = roofline.peaks_for(device_kind)
    flops = sum(t["flops"] for t in terms)
    nbytes = sum(t["bytes"] for t in terms)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "flops": flops,
            "bytes": nbytes, "seconds_by_flops": by_flops,
            "seconds_by_bytes": by_bytes,
            "bound": "bytes" if by_bytes >= by_flops else "flops"}


def moe_experts_floor(config: Dict, tokens: float, assignments: float,
                      experts_hit: float, device_kind: str) -> Dict:
    return _floor(moe_experts_terms(config, tokens, assignments,
                                    experts_hit), device_kind)


def ssd_scan_floor(config: Dict, tokens: float, device_kind: str) -> Dict:
    return _floor(ssd_scan_terms(config, tokens), device_kind)


def forward_flops_per_token(config: Dict, context: float) -> Dict[str, float]:
    """Matmul flops a real token of the forward pass as held here
    (2 a multiply-add), by layer kind, for the predictions in PERF.md:
    `context` is the mean number of earlier positions a token attends."""
    h = config["hidden_size"]
    nh, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    di = nh * p
    mamba = 2 * h * (2 * di + 2 * g * n + nh) + 2 * di * h + sum(
        t["flops"] for t in ssd_scan_terms(config, 1.0))
    hq, hkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    attn = 2 * h * (hq + 2 * hkv) * d + 2 * hq * d * h \
        + 2 * 2 * hq * d * context
    lat, w = config["moe_latent_size"], config["moe_intermediate_size"]
    sw = config["moe_shared_expert_intermediate_size"]
    share = config["experts_held"] / config["n_routed_experts"]
    experts = (2 * h * config["n_routed_experts"] + 2 * 2 * h * lat
               + 2 * 2 * lat * w * config["num_experts_per_tok"] * share
               + 2 * 2 * h * sw)
    count = {k: config["pattern"].count(k) for k in "M*E"}
    return {"M": mamba * count["M"], "*": attn * count["*"],
            "E": experts * count["E"]}
