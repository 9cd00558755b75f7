#!/usr/bin/env python3
"""The open-loop load generator: a JAX-free child process that sends
`POST /predict` on a fixed schedule, whatever the server does.

    python3 benchmarks/loadgen.py --plan plan.json --out results.json

`plan.json`: {"port", "deadline_ms", "threads", "requests": [{"due_s",
"file", "keep_body"}]}. The child reads every body into memory, prints
`READY`, and waits on stdin for `GO <epoch seconds>`: the instant the
schedule's zero falls on. A request's latency counts from the instant
it was DUE, not from when a thread got round to sending it, so a stall
charges every request it delays; how late each was sent is reported
beside it. A refused, failed, late (past its deadline) or malformed
answer is `ok: false`.

`schedule()` is the one general generator of arrival plans: the traffic
file gives the rate, the seed of the gaps and the pool of bodies; every
seed gets the SAME arrival instants and the same files, the files in
another order, so that the seed does not change the amount of work (on
the chip, permuting the gaps too moved the 95th percentile by 18 % from
seed to seed, against 2-8 % between two runs of one seed; PERF.md).
"""

from __future__ import annotations

import argparse
import http.client
import json
import queue
import random
import sys
import threading
import time
from typing import Dict, List


def schedule(seed: int, seconds: float, traffic: Dict) -> List[Dict]:
    """`round(rate * seconds)` arrivals inside `seconds`: the first n of
    the traffic's own fixed gap list (exponential gaps drawn from
    `schedule_seed`: Poisson arrivals), rescaled to fill the window;
    the first n bodies of the pool, permuted by `seed`. Returns
    [{"due_s", "body_index"}] in due order; no body repeats."""
    rate = float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    if n > int(traffic["request_pool"]):
        raise ValueError(f"{n} requests need a pool above "
                         f"{traffic['request_pool']} bodies")
    fixed = random.Random(int(traffic["schedule_seed"]))
    gaps = [fixed.expovariate(rate) for _ in range(n)]
    scale = seconds / (sum(gaps) + fixed.expovariate(rate))
    bodies = list(range(n))
    random.Random(int(seed)).shuffle(bodies)
    out, t = [], 0.0
    for gap, body in zip(gaps, bodies):
        t += gap * scale
        out.append({"due_s": t, "body_index": body})
    return out


def well_formed(payload) -> int:
    """Number of methods of a well-formed /predict answer, else -1."""
    try:
        methods = payload["methods"]
        for m in methods:
            if not isinstance(m["original_name"], str):
                return -1
            for p in m["predictions"]:
                if not (isinstance(p["name"], list)
                        and 0.0 <= float(p["probability"]) <= 1.0 + 1e-6):
                    return -1
        return len(methods)
    except (KeyError, TypeError, ValueError):
        return -1


def _send(port: int, body: bytes, timeout: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/predict", body=body,
                     headers={"Content-Type": "text/plain"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def run(plan: Dict, t0_epoch: float, bodies: List[bytes]) -> List[Dict]:
    """Send every request at its due instant from `threads` workers."""
    anchor = t0_epoch - time.time() + time.perf_counter()
    deadline_s = plan["deadline_ms"] / 1000.0
    port = plan["port"]
    work: "queue.Queue" = queue.Queue()
    results: List[Dict] = [None] * len(plan["requests"])  # type: ignore

    def worker():
        while True:
            i = work.get()
            if i is None:
                return
            req = plan["requests"][i]
            due = anchor + req["due_s"]
            sent = time.perf_counter()
            status, raw, error = 0, b"", ""
            try:
                status, raw = _send(port, bodies[i], deadline_s + 10.0)
            except (OSError, http.client.HTTPException) as e:
                error = f"{type(e).__name__}: {e}"
            done = time.perf_counter()
            methods = -1
            if status == 200:
                try:
                    methods = well_formed(json.loads(raw))
                except ValueError:
                    methods = -1
            latency = done - due
            ok = status == 200 and methods >= 0 and latency <= deadline_s
            out = {"i": i, "due_s": req["due_s"], "late_ms": (sent - due) * 1e3,
                   "latency_ms": latency * 1e3, "status": status, "ok": ok,
                   "methods": methods}
            if error:
                out["error"] = error
            if req.get("keep_body") and status == 200:
                out["body"] = raw.decode("utf-8", "replace")
            results[i] = out

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(plan["threads"]))]
    for t in threads:
        t.start()
    for i, req in enumerate(plan["requests"]):
        wait = anchor + req["due_s"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        work.put(i)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout=deadline_s + 30.0)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.plan) as f:
        plan = json.load(f)
    bodies = []
    for req in plan["requests"]:
        with open(req["file"], "rb") as f:
            bodies.append(f.read())
    print("READY", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "GO":
        return 2
    results = run(plan, float(line[1]), bodies)
    with open(args.out, "w") as f:
        json.dump(results, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
