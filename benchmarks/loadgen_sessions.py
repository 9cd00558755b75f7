#!/usr/bin/env python3
"""The open-loop generator of `loadgen.py`, sending TURNS of sessions
that grow: `POST /score {context, ids, top_k, keep: true}`.

    python3 benchmarks/loadgen_sessions.py --plan plan.json --out results.json

`loadgen.py`'s clock and results (READY, `GO <epoch seconds>`, a
request's latency counted from the instant it was DUE, `late_ms` beside
it), `loadgen_lm.py`'s test of a well-formed answer, and one thing more:
a turn names the id its session's PREVIOUS turn answered with. `plan`:
{"port", "deadline_ms", "threads", "sessions": [the id each session has
when the window opens], "requests": [{"due_s", "session", "ids", "top_k",
"keep", "keep_body"}]}, in due order. A turn whose session's previous
turn has not answered yet waits for that answer, is sent when it
arrives, and is still timed from its due instant. A turn with `keep`
false is a plain question: it names the session's id and leaves it as it
is. A result also says which id the turn `named` and which id came back
(`context`), so that the runner can lay every session's tokens end to
end in the order the server kept them.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import queue
import sys
import threading
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.loadgen_lm import _send, well_formed  # noqa: E402


def run(plan: Dict, t0_epoch: float) -> List[Dict]:
    """Send every turn at its due instant, or when its session's turn
    before it has answered, from `threads` workers."""
    anchor = t0_epoch - time.time() + time.perf_counter()
    deadline_s = plan["deadline_ms"] / 1000.0
    port, requests = plan["port"], plan["requests"]
    current = list(plan["sessions"])        # a session's id, now
    tails = [json.dumps({"ids": r["ids"], "top_k": r["top_k"],
                         "return_routing": True, **(
                             {"keep": True} if r["keep"] else {})})[1:]
             for r in requests]
    answered = [threading.Event() for _ in requests]
    before: List[int] = []                  # the session's turn before
    last: Dict[int, int] = {}
    for i, r in enumerate(requests):
        before.append(last.get(r["session"], -1))
        last[r["session"]] = i
    work: "queue.Queue" = queue.Queue()
    results: List[Dict] = [None] * len(requests)  # type: ignore

    def worker():
        while True:
            i = work.get()
            if i is None:
                return
            req = requests[i]
            due = anchor + req["due_s"]
            if before[i] >= 0:
                answered[before[i]].wait(timeout=deadline_s + 10.0)
            named = current[req["session"]]
            body = ('{"context": "%s", ' % named + tails[i]).encode()
            sent = time.perf_counter()
            status, raw, error, answer = 0, b"", "", None
            try:
                status, raw = _send(port, body, deadline_s + 10.0)
            except (OSError, http.client.HTTPException) as e:
                error = f"{type(e).__name__}: {e}"
            done = time.perf_counter()
            tokens = -1
            if status == 200:
                try:
                    answer = json.loads(raw)
                    tokens = well_formed(answer)
                except ValueError:
                    tokens = -1
            kept = None
            if req["keep"] and tokens >= 0:
                kept = answer.get("context")
                if isinstance(kept, str) and kept != named:
                    current[req["session"]] = kept
                else:
                    tokens = -1         # a kept turn answers with a new id
            answered[i].set()
            latency = done - due
            ok = status == 200 and tokens >= 0 and latency <= deadline_s
            out = {"i": i, "due_s": req["due_s"],
                   "late_ms": (sent - due) * 1e3,
                   "latency_ms": latency * 1e3, "status": status, "ok": ok,
                   "methods": tokens, "session": req["session"],
                   "named": named, "context": kept}
            if error:
                out["error"] = error
            if status != 200:
                out["refusal"] = raw.decode("utf-8", "replace")[:300]
            if req.get("keep_body") and status == 200:
                out["body"] = raw.decode("utf-8", "replace")
            results[i] = out

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(plan["threads"]))]
    for t in threads:
        t.start()
    for i, req in enumerate(requests):
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
    print("READY", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "GO":
        return 2
    results = run(plan, float(line[1]))
    with open(args.out, "w") as f:
        json.dump(results, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
