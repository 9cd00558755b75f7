#!/usr/bin/env python3
"""The open-loop generator of `loadgen.py`, sending `POST /score`.

    python3 benchmarks/loadgen_lm.py --plan plan.json --out results.json

Same plan, same clock, same results as `loadgen.py` (whose `run` and
`main` do the work here): only the request and what makes an answer
well formed differ. A body is a JSON file `{"ids": [...], "top_k": N,
"return_routing": true}`; an answer is well formed when `top` holds
`top_k` distinct token ids with finite logits and probabilities in
[0, 1]; its `methods` field then counts the tokens answered.
"""

from __future__ import annotations

import http.client
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loadgen  # noqa: E402


def well_formed(payload) -> int:
    """Number of tokens of a well-formed /score answer, else -1."""
    try:
        top = payload["top"]
        ids = [int(t["id"]) for t in top]
        for t in top:
            if not (math.isfinite(float(t["logit"]))
                    and 0.0 <= float(t["probability"]) <= 1.0 + 1e-6):
                return -1
        if not ids or len(set(ids)) != len(ids) or int(payload["tokens"]) < 1:
            return -1
        return len(ids)
    except (KeyError, TypeError, ValueError):
        return -1


def _send(port: int, body: bytes, timeout: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/score", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def main(argv=None) -> int:
    loadgen.well_formed = well_formed
    loadgen._send = _send
    return loadgen.main(argv)


if __name__ == "__main__":
    sys.exit(main())
