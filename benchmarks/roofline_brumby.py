"""The least time one chip could take for the power-retention model's
scoring step and for its state reads, from the configuration's shapes
and the step's own counts, against the chip's published peaks
(`peaks.json`, through `roofline.peaks_for`).

Each floor counts only what ANY implementation of the stated layer has
to do for the REAL tokens of a step (padding needs nothing), whatever
implements it: no feature map of the queries written and read back, no
bfloat16 copy of a state, no selection matmuls. So a share cannot pass
100 %. The roofline counts the 8,256 features of the symmetric square,
whatever a layout pads them to.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.roofline_lm import _floor


def _features(config: Dict) -> int:
    d = config["head_dim"]
    return d * (d + 1) // 2


def retention_read_terms(config: Dict, rows: float, tokens: float,
                         pairs: float) -> List[Dict]:
    """One layer's retention of one step AS A SCORING STEP RUNS IT (the
    state is read, never written): `rows` rows that read a context's
    state, `tokens` real question tokens over all rows, `pairs` (query,
    key <= query) pairs inside the rows' own tokens."""
    hq, hkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    e, f = d + 1, _features(config)
    return [
        {"term": "each row's [S | z], every key/value head, float32, read "
                 "once a layer",
         "flops": 0, "bytes": rows * hkv * e * f * 4},
        {"term": "phi(q) against [S | z]: features x (values + the "
                 "normaliser) multiply-adds a (real token, query head), 2 "
                 "flops each",
         "flops": 2 * f * e * hq * tokens, "bytes": 0},
        {"term": "the rows' own tokens: q . k and the weighted sum over "
                 "[V | 1] a (query, key <= query) pair and query head",
         "flops": 2 * (d + e) * hq * pairs, "bytes": 0},
        {"term": "q, k, v in and y out, once a real token (bfloat16)",
         "flops": 0, "bytes": tokens * (2 * hq + 2 * hkv) * d * 2},
    ]


def dense_layer_params(config: Dict) -> int:
    """The matrices every real token passes in one layer."""
    h, d, w = (config["hidden_size"], config["head_dim"],
               config["intermediate_size"])
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * h * hq * d + 2 * h * hkv * d + h * hkv + 3 * h * w


def score_step_terms(config: Dict, step_rows: float, rows: float,
                     tokens: float, pairs: float) -> List[Dict]:
    """The WHOLE scoring step: `step_rows` real rows (each answered by
    the head), of which `rows` read a context's state; `tokens` real
    tokens; `pairs` as above. Every layer held, the embedding's rows and
    the head."""
    layers, h, v = (config["layers"], config["hidden_size"],
                    config["vocab_rows"])
    dense = dense_layer_params(config)
    out = [
        {"term": "the layers' matrices (q, k, v, o, the gate, the gated "
                 "MLP), bfloat16, read once a step",
         "flops": 0, "bytes": layers * dense * 2},
        {"term": "those matrices against every real token, 2 flops a "
                 "multiply-add",
         "flops": 2 * layers * dense * tokens, "bytes": 0},
        {"term": "the head: every row held against each real row's last "
                 "state, its matrix (bfloat16) read once",
         "flops": 2 * v * h * step_rows, "bytes": v * h * 2},
        {"term": "the embedding's rows of the real tokens",
         "flops": 0, "bytes": tokens * h * 2},
    ]
    for term in retention_read_terms(config, rows, tokens, pairs):
        out.append({"term": f"{layers} layers x " + term["term"],
                    "flops": layers * term["flops"],
                    "bytes": layers * term["bytes"]})
    return out


def retention_read_floor(config: Dict, rows: float, tokens: float,
                         pairs: float, device_kind: str) -> Dict:
    return _floor(retention_read_terms(config, rows, tokens, pairs),
                  device_kind)


def score_step_floor(config: Dict, step_rows: float, rows: float,
                     tokens: float, pairs: float, device_kind: str) -> Dict:
    return _floor(score_step_terms(config, step_rows, rows, tokens, pairs),
                  device_kind)
