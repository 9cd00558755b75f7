#!/usr/bin/env python3
"""Does the system still start on the chip? One pass over the main path.

`python chip_smoke.py`, from the root of a checkout, on a machine with
one TPU chip. It drives the flagship (java14m) width — 1,301,136 token /
911,417 path / 261,245 target rows, 128/128/384 dims, 383,672,704
parameters, batch 1024, 200 contexts — through the entry points a user
calls, each phase a child process of its own so that only one process
at a time holds the chip; this parent never imports jax or the package:

  probe     ask JAX which device it resolves; anything but `tpu` stops here
  build     `make -C cpp` into a clean cpp/build (extractor + data loader)
  generate  seeded flagship-width dictionaries and a few thousand rows
  train     `code2vec.py --data --test --save --epochs 1`: host-fed steps,
            Orbax save, end-of-epoch eval through the blockwise head
  eval      `code2vec.py --load --test`: the restored checkpoint must
            reproduce the trainer's own evaluation
  eval_full the same with `--topk_block 0` (one materialized
            (B, 261245) logit row + `lax.top_k`): the blockwise head's
            reference
  serve     `code2vec.py serve --load`, POST /predict with Java source,
            GET /healthz, SIGTERM, exit code 0 from the drain

Every child says in its own log which device its parameters live on and
its peak device memory; this script reads those lines, never an
environment variable. Any failed, late or malformed phase ends the run
non-zero with the child's log tail. The last line of stdout is one JSON
object, printed only when every phase passed. Times printed here are
set-up facts of a smoke, not performance records.
"""

from __future__ import annotations

import json
import math
import os
import pickle
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")   # git-ignored; rebuilt each run
LOGS = os.path.join(WORK, "logs")
DATA_PREFIX = os.path.join(WORK, "data", "flagship")
MODEL_BASE = os.path.join(WORK, "model", "saved_model")
EXTRACTOR = os.path.join(REPO, "cpp", "build", "c2v-extract")
LOADER = os.path.join(REPO, "cpp", "build", "libc2vdata.so")
MANIFEST_NAME = "code2vec_manifest.json"

SEED = 21
TOKEN_ROWS, PATH_ROWS, TARGET_ROWS = 1_301_136, 911_417, 261_245
FLAGSHIP_PARAMS = 383_672_704
BATCH = 1024
CONTEXTS = 200
TRAIN_STEPS = 8
VAL_ROWS = BATCH + BATCH // 2      # one full and one half-padded batch
FAMILIES = 64                      # label-revealing context families

# The driver allows 1200 s in all; every phase draws on what is left.
TOTAL_BUDGET_S = 1150.0
_T0 = time.monotonic()

_DEVICE_RE = re.compile(
    r'device: platform=(\S+) kind="([^"]*)" devices=(\d+)'
    r'(?: params_on=(\d+))? peak_bytes_in_use=(\S+)'
    r' peak_bytes_reserved=(\S+)')
_EVAL_RE = re.compile(
    r"loss: ([-+.\deinfna]+), top(?:k|\d+)_acc: \[([^\]]*)\], "
    r"precision: ([-+.\deinfna]+), recall: ([-+.\deinfna]+), "
    r"F1: ([-+.\deinfna]+)")


class PhaseFailed(Exception):
    """A phase failed, ran late or answered malformed."""


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def _remaining() -> float:
    return TOTAL_BUDGET_S - (time.monotonic() - _T0)


def _tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def _reap_group(proc: subprocess.Popen) -> None:
    """Every child leads its own process group, so whatever it started
    (extractor workers, make's compilers) ends with it — also after a
    clean exit, when the group is normally already empty."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()


def run_phase(name: str, cmd: List[str], timeout: float) -> Tuple[str, float]:
    """Run one child to completion with its output in LOGS/<name>.log.
    Returns (log text, wall seconds); raises PhaseFailed on a non-zero
    exit or a timeout, with the log tail in the message."""
    os.makedirs(LOGS, exist_ok=True)
    log_path = os.path.join(LOGS, f"{name}.log")
    timeout = min(timeout, _remaining())
    if timeout <= 0:
        raise PhaseFailed(f"{name}: no time left in the "
                          f"{TOTAL_BUDGET_S:.0f}s budget")
    t0 = time.monotonic()
    with open(log_path, "wb") as logf:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(
                f"{name}: timed out after {timeout:.0f}s\n{_tail(log_path)}")
        finally:
            _reap_group(proc)
    seconds = time.monotonic() - t0
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}\n{_tail(log_path)}")
    with open(log_path, errors="replace") as f:
        return f.read(), seconds


def check(cond: bool, phase: str, what: str, log_name: Optional[str] = None
          ) -> None:
    if not cond:
        tail = (_tail(os.path.join(LOGS, f"{log_name}.log"))
                if log_name else "")
        raise PhaseFailed(f"{phase}: {what}\n{tail}")


# ------------------------------------------------------------------ probe

_PROBE = (
    "import json, jax\n"
    "d = jax.devices()\n"
    "print('PROBE ' + json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))\n")


def probe_device() -> Dict:
    """What JAX itself resolves, asked in a child (this parent must not
    hold the chip). The ambient JAX_PLATFORMS is left exactly as found:
    if it points JAX at the CPU, that is what the probe reports and the
    run stops — it is never unset or overridden here."""
    try:
        text, _ = run_phase("probe", [sys.executable, "-c", _PROBE], 180.0)
    except PhaseFailed as e:
        raise PhaseFailed(
            f"JAX found no usable device (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}): {e}")
    lines = [ln for ln in text.splitlines() if ln.startswith("PROBE ")]
    check(bool(lines), "probe", "no PROBE line", "probe")
    device = json.loads(lines[-1][len("PROBE "):])
    if device["platform"] != "tpu":
        raise PhaseFailed(
            f"probe: JAX resolved platform '{device['platform']}' "
            f"({device['kind']} x{device['count']}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}). chip_smoke.py "
            f"checks the TPU path only and does not fall back.")
    return device


# ------------------------------------------------------------------ build

def build_native() -> float:
    """c2v-extract and libc2vdata.so from cpp/src into a CLEAN cpp/build:
    the driver's checkout has no build directory and a copied one may
    be stale."""
    shutil.rmtree(os.path.join(REPO, "cpp", "build"), ignore_errors=True)
    _, seconds = run_phase(
        "build", ["make", "-C", os.path.join(REPO, "cpp"),
                  f"-j{os.cpu_count() or 1}"], 600.0)
    for path in (EXTRACTOR, LOADER):
        check(os.path.isfile(path), "build", f"{path} was not built",
              "build")
    return seconds


# --------------------------------------------------------------- generate

def _subtoken_words(rng: random.Random, n: int) -> List[str]:
    """n distinct letters-only pseudo-words: predicted names must pass
    the reference's legality filter ^[a-zA-Z|]+$."""
    syl = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]
    words = [a + b for a in syl for b in syl]
    rng.shuffle(words)
    return words[:n]


def _extract(path: str) -> Tuple[str, List[Tuple[str, str, str]]]:
    """(method name, contexts) of the first method in a Java file, from
    the extractor this run built — hashed paths, the training format."""
    out = subprocess.run(
        [EXTRACTOR, "--max_path_length", "8", "--max_path_width", "2",
         "--file", path], capture_output=True, text=True, timeout=60,
        check=True).stdout
    parts = out.splitlines()[0].split(" ")
    return parts[0], [tuple(c.split(",")) for c in parts[1:] if c]


def generate_data() -> Dict:
    """Seeded flagship-width `.dict.c2v` + `.train.c2v` / `.val.c2v`.
    Model dims come from the dictionaries, so the width is real only
    because these are. Rows are learnable (each family's tokens and
    paths reveal its label) with heavy-tailed context counts; family 0
    is built from the real contexts of Input.java, so the serve phase's
    request is in-vocabulary."""
    rng = random.Random(SEED)
    shutil.rmtree(os.path.join(WORK, "data"), ignore_errors=True)
    shutil.rmtree(os.path.join(WORK, "model"), ignore_errors=True)
    os.makedirs(os.path.dirname(DATA_PREFIX))
    real_name, real_ctx = _extract(os.path.join(REPO, "Input.java"))
    real_tokens = sorted({t for s, _, t2 in real_ctx for t in (s, t2)})
    real_paths = sorted({p for _, p, _ in real_ctx})

    def vocabulary(real: List[str], rows: int, word) -> List[str]:
        """The real words, then word(0), word(1), ... (distinct by
        construction) up to rows - 1: one special word per vocabulary."""
        words, taken, i = list(real), set(real), 0
        while len(words) < rows - 1:
            if word(i) not in taken:
                words.append(word(i))
            i += 1
        return words

    sub = _subtoken_words(rng, 512)
    tokens = vocabulary(real_tokens, TOKEN_ROWS, lambda i: f"tok{i}")
    # odd multiplier: a bijection on 32-bit ints, like a hash column
    paths = vocabulary(
        real_paths, PATH_ROWS,
        lambda i: str((i * 2654435761) % (1 << 32) - (1 << 31)))
    targets = vocabulary([real_name], TARGET_ROWS,
                         lambda i: f"{sub[i // 512]}|{sub[i % 512]}")

    fam_label = [real_name] + rng.sample(targets[1:], FAMILIES - 1)
    fam_tokens = [real_tokens] + [rng.sample(tokens, 24)
                                  for _ in range(FAMILIES - 1)]
    fam_paths = [real_paths] + [rng.sample(paths, 12)
                                for _ in range(FAMILIES - 1)]
    in_family = set(fam_label)
    for pool in fam_tokens + fam_paths:
        in_family.update(pool)

    def counts(words):
        return {w: (100 if w in in_family else 2) for w in words}

    def row(label: str, fam: int) -> str:
        n = min(CONTEXTS, max(4, int(rng.lognormvariate(4.3, 0.7))))
        ctx = []
        for _ in range(n):
            if rng.random() < 0.7:
                ctx.append(f"{rng.choice(fam_tokens[fam])},"
                           f"{rng.choice(fam_paths[fam])},"
                           f"{rng.choice(fam_tokens[fam])}")
            else:   # anywhere in the tables: wide-range gathers
                ctx.append(f"{rng.choice(tokens)},{rng.choice(paths)},"
                           f"{rng.choice(tokens)}")
        return f"{label} " + " ".join(ctx) + " " * (CONTEXTS - n)

    n_train = TRAIN_STEPS * BATCH
    with open(DATA_PREFIX + ".train.c2v", "w") as f:
        for _ in range(n_train):
            fam = rng.randrange(FAMILIES)
            f.write(row(fam_label[fam], fam) + "\n")
    with open(DATA_PREFIX + ".val.c2v", "w") as f:
        for _ in range(VAL_ROWS):
            fam = rng.randrange(FAMILIES)
            # ~3% names the vocabulary has never seen
            label = ("zuzu|unseen|name" if rng.random() < 0.03
                     else fam_label[fam])
            f.write(row(label, fam) + "\n")
    with open(DATA_PREFIX + ".dict.c2v", "wb") as f:
        pickle.dump(counts(tokens), f)
        pickle.dump(counts(paths), f)
        pickle.dump(counts(targets), f)
        pickle.dump(n_train, f)
    return {"real_name": real_name, "real_contexts": len(real_ctx),
            "train_rows": n_train, "val_rows": VAL_ROWS}


# ------------------------------------------------------------ log reading

def _device_lines(text: str, marker: str) -> List[Dict]:
    out = []
    for line in text.splitlines():
        if marker in line:
            m = _DEVICE_RE.search(line)
            if m:
                out.append({"platform": m.group(1), "kind": m.group(2),
                            "count": int(m.group(3)),
                            "params_on": (int(m.group(4)) if m.group(4)
                                          else None),
                            # live arrays + what compiled programs
                            # reserved for their temporaries
                            "peak_bytes": (
                                None if "n/a" in (m.group(5), m.group(6))
                                else int(m.group(5)) + int(m.group(6)))})
    return out


def require_device(text: str, marker: str, phase: str, probe: Dict) -> Dict:
    """The child's own report of where its parameters live must name the
    chip the probe saw; returns the LAST such line (the largest peak)."""
    found = _device_lines(text, marker)
    check(bool(found), phase, f"no '{marker}' device line", phase)
    for d in found:
        check(d["platform"] == "tpu", phase,
              f"'{marker}' ran on platform {d['platform']!r}, not tpu",
              phase)
        check((d["kind"], d["count"]) == (probe["kind"], probe["count"]),
              phase, f"'{marker}' reports {d['kind']} x{d['count']}, the "
              f"probe saw {probe['kind']} x{probe['count']}", phase)
    last = found[-1]
    check(last["peak_bytes"] is not None and last["peak_bytes"] > 0, phase,
          f"'{marker}' reports no peak device memory", phase)
    return last


def parse_eval(text: str, phase: str) -> Dict:
    found = list(_EVAL_RE.finditer(text))
    check(bool(found), phase, "no evaluation result line", phase)
    m = found[-1]
    res = {"loss": float(m.group(1)),
           "topk_acc": [float(x) for x in m.group(2).split()],
           "precision": float(m.group(3)), "recall": float(m.group(4)),
           "f1": float(m.group(5))}
    acc = res["topk_acc"]
    check(math.isfinite(res["loss"]) and 0.0 < res["loss"]
          < math.log(TARGET_ROWS) + 1.0, phase,
          f"eval loss {res['loss']} outside (0, ln(V)+1)", phase)
    check(len(acc) == 10 and all(0.0 <= a <= 1.0 for a in acc)
          and all(b >= a for a, b in zip(acc, acc[1:])), phase,
          f"top-k accuracies malformed: {acc}", phase)
    check(all(0.0 <= res[k] <= 1.0 for k in ("precision", "recall", "f1")),
          phase, f"subtoken metrics malformed: {res}", phase)
    return res


def _gib(n: Optional[int]) -> str:
    return "n/a" if n is None else f"{n / (1 << 30):.2f} GiB"


# ------------------------------------------------------------------ train

def train_phase(probe: Dict) -> Dict:
    cmd = [sys.executable, os.path.join(REPO, "code2vec.py"),
           "--data", DATA_PREFIX, "--test", DATA_PREFIX + ".val.c2v",
           "--save", MODEL_BASE, "--epochs", "1",
           "--batch_size", str(BATCH)]
    text, seconds = run_phase("train", cmd, 700.0)
    m = re.search(r"Model created: ([\d,]+) parameters", text)
    check(m is not None, "train", "no 'Model created' line", "train")
    n_params = int(m.group(1).replace(",", ""))
    check(n_params == FLAGSHIP_PARAMS, "train",
          f"{n_params:,} parameters, not the flagship "
          f"{FLAGSHIP_PARAMS:,}", "train")
    created = require_device(text, "Model created:", "train", probe)
    check(created["params_on"] == 1, "train",
          f"parameters on {created['params_on']} devices, expected 1",
          "train")
    check("one-time; native libc2vdata.so" in text, "train",
          "the .c2v pack did not run through the native loader", "train")
    m = re.search(r"First train step dispatched in ([\d.]+)s", text)
    check(m is not None, "train", "no first-step line", "train")
    first_step_s = float(m.group(1))
    m = re.search(r"Epoch 1 ended after (\d+) batches; loss over the last "
                  r"(\d+): ([-+.\deinfna]+) -> ([-+.\deinfna]+)", text)
    check(m is not None, "train", "no end-of-epoch loss line", "train")
    steps, first, last = int(m.group(1)), float(m.group(3)), float(m.group(4))
    check(steps == TRAIN_STEPS and int(m.group(2)) == TRAIN_STEPS, "train",
          f"{steps} steps, expected {TRAIN_STEPS}", "train")
    # Untrained, the 261,245-way softmax sits near ln(V) = 12.47: a head
    # that overflowed, mis-masked or mis-scaled would not.
    check(math.isfinite(first) and abs(first - math.log(TARGET_ROWS)) < 0.5,
          "train", f"first loss {first} is not near ln({TARGET_ROWS}) = "
          f"{math.log(TARGET_ROWS):.3f}", "train")
    check(math.isfinite(last) and last < first, "train",
          f"loss did not fall: {first} -> {last}", "train")
    epoch_dev = require_device(text, "Epoch 1 ended", "train", probe)
    eval_dev = require_device(text, "Evaluation pass done", "train", probe)
    res = parse_eval(text, "train")
    # Each family's contexts reveal its label; ~3% of the val names are
    # out of vocabulary. Steps that did not learn stay near 1/261,245.
    check(res["topk_acc"][0] >= 0.5, "train",
          f"top-1 {res['topk_acc'][0]} after {TRAIN_STEPS} steps: the "
          f"label-revealing families were not learned", "train")
    ckpts = [MODEL_BASE + "_iter1", MODEL_BASE]
    for ckpt in ckpts:
        check(os.path.isfile(os.path.join(ckpt, MANIFEST_NAME)), "train",
              f"checkpoint {ckpt} has no {MANIFEST_NAME}", "train")
    say(f"train: {n_params:,} parameters on {created['kind']} "
        f"x{created['count']} ({created['platform']}); {steps} steps, loss "
        f"{first:.4f} -> {last:.4f}; first step (compile or cache load) "
        f"{first_step_s:.2f}s; peak device memory after the steps "
        f"{_gib(epoch_dev['peak_bytes'])}, after save+eval "
        f"{_gib(eval_dev['peak_bytes'])}; eval (blockwise head) loss "
        f"{res['loss']:.4f} top1 {res['topk_acc'][0]:.4f} top10 "
        f"{res['topk_acc'][-1]:.4f} F1 {res['f1']:.4f}; checkpoints with "
        f"manifest: {len(ckpts)}; loader: native libc2vdata.so; "
        f"{seconds:.1f}s")
    return {"seconds": seconds, "first_step_s": first_step_s,
            "loss_first": first, "loss_last": last, "eval": res,
            "peak_train": epoch_dev["peak_bytes"],
            "peak_train_eval": eval_dev["peak_bytes"]}


# ------------------------------------------------------------------- eval

def eval_phase(name: str, probe: Dict, extra: List[str]) -> Dict:
    cmd = [sys.executable, os.path.join(REPO, "code2vec.py"),
           "--load", MODEL_BASE + "_iter1",
           "--test", DATA_PREFIX + ".val.c2v",
           "--batch_size", str(BATCH)] + extra
    text, seconds = run_phase(name, cmd, 400.0)
    check("Loaded model weights from" in text, name,
          "no checkpoint-restore line", name)
    dev = require_device(text, "Evaluation pass done", name, probe)
    res = parse_eval(text, name)
    say(f"{name}: restored {MODEL_BASE}_iter1 on {dev['kind']} "
        f"x{dev['count']} ({dev['platform']}); loss {res['loss']:.4f} top1 "
        f"{res['topk_acc'][0]:.4f} top10 {res['topk_acc'][-1]:.4f} F1 "
        f"{res['f1']:.4f}; peak device memory "
        f"{_gib(dev['peak_bytes'])}; {seconds:.1f}s")
    return {"seconds": seconds, "eval": res, "peak": dev["peak_bytes"]}


def same_eval(a: Dict, b: Dict, what: str, phase: str) -> None:
    """Two evaluations of the same weights on the same rows. The loss
    is a mean of f32 log-sum-exps over bf16 logits, so 1e-3 relative
    is far inside what a wrong block, mask or restore would move; a
    count may differ by the odd near-tie."""
    check(abs(a["loss"] - b["loss"]) <= 1e-3 * abs(b["loss"]), phase,
          f"{what}: loss {a['loss']} vs {b['loss']}", phase)
    for x, y in zip(a["topk_acc"] + [a["f1"]], b["topk_acc"] + [b["f1"]]):
        check(abs(x - y) <= 0.005, phase,
              f"{what}: metrics differ: {a} vs {b}", phase)


# ------------------------------------------------------------------ serve

_SMALL_JAVA = ("class A { int getCount() { return this.count; } "
               "int count; }\n")


def _big_java() -> str:
    """One method with enough leaves for the 200-context bucket."""
    body = "".join(f"        total += values[{i}] * weight{i};\n"
                   for i in range(12))
    fields = "".join(f"    int weight{i};\n" for i in range(12))
    return ("class Big {\n    int[] values;\n" + fields
            + "    int weightedTotal() {\n        int total = 0;\n" + body
            + "        return total;\n    }\n}\n")


def _http(method: str, url: str, body: Optional[bytes] = None,
          timeout: float = 300.0) -> Tuple[int, bytes]:
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "text/plain")
        # `serve` warms every predict bucket before it listens, so no
        # request should meet a compile; the generous client deadline
        # stays so that a cold extractor or a slow first transfer fails
        # a check with a message and not with a 504.
        req.add_header("X-Deadline-Ms", "280000")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _check_prediction(payload: Dict, label: str) -> str:
    methods = payload.get("methods")
    check(isinstance(methods, list) and methods, "serve",
          f"{label}: no methods in {payload}", "serve")
    top = ""
    for method in methods:
        preds = method.get("predictions")
        check(isinstance(method.get("original_name"), str)
              and isinstance(preds, list) and 1 <= len(preds) <= 10,
              "serve", f"{label}: malformed method {method}", "serve")
        probs = [p["probability"] for p in preds]
        check(all(isinstance(p["name"], list) and p["name"]
                  and all(isinstance(s, str) and s for s in p["name"])
                  for p in preds), "serve",
              f"{label}: malformed names {preds}", "serve")
        check(all(isinstance(q, float) and math.isfinite(q)
                  and 0.0 < q <= 1.0 for q in probs)
              and all(b <= a for a, b in zip(probs, probs[1:]))
              and sum(probs) <= 1.0 + 1e-3, "serve",
              f"{label}: malformed probabilities {probs}", "serve")
        top = top or "|".join(preds[0]["name"])
    return top


def serve_phase(probe: Dict, real_name: str) -> Dict:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, os.path.join(REPO, "code2vec.py"), "serve",
           "--load", MODEL_BASE, "--serve_port", str(port),
           "--serve_deadline_max_ms", "300000"]
    os.makedirs(LOGS, exist_ok=True)
    log_path = os.path.join(LOGS, "serve.log")
    budget = min(500.0, _remaining())
    check(budget > 0, "serve", "no time left in the budget")
    t0 = time.monotonic()
    base = f"http://127.0.0.1:{port}"
    with open(log_path, "wb") as logf:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        def log_text() -> str:
            with open(log_path, errors="replace") as f:
                return f.read()

        while "Prediction server listening on" not in log_text():
            check(proc.poll() is None, "serve",
                  f"server exited rc={proc.returncode} before it was ready",
                  "serve")
            check(time.monotonic() - t0 < budget, "serve",
                  "server not ready in time", "serve")
            time.sleep(0.5)
        ready_s = time.monotonic() - t0
        ready = require_device(log_text(), "Prediction server listening on",
                               "serve", probe)
        check(ready["params_on"] == 1, "serve",
              f"parameters on {ready['params_on']} devices", "serve")
        pool = re.search(r"Extractor pool up: (.*)", log_text())
        check(pool is not None and "warm --server" in pool.group(1)
              and EXTRACTOR in pool.group(1), "serve",
              f"extractor pool is not the warm pool of {EXTRACTOR}: "
              f"{pool.group(1) if pool else None}", "serve")

        with open(os.path.join(REPO, "Input.java"), "rb") as f:
            input_java = f.read()
        requests = [("Input.java", input_java),
                    ("small", _SMALL_JAVA.encode()),
                    ("big", _big_java().encode()),
                    ("Input.java again", input_java)]
        answers = []
        for label, body in requests:
            t_req = time.monotonic()
            left = budget - (time.monotonic() - t0)
            status, raw = _http("POST", base + "/predict", body,
                                timeout=max(left, 1.0))
            check(status == 200, "serve",
                  f"POST /predict ({label}) answered {status}: "
                  f"{raw[:300]!r}", "serve")
            top = _check_prediction(json.loads(raw), label)
            # Family 0 was trained on Input.java's own contexts: the
            # whole chain (train, save, load, extract, predict, render)
            # is right only if its method gets its name back.
            check(not label.startswith("Input.java") or top == real_name,
                  "serve", f"{label}: top prediction '{top}', trained "
                  f"name '{real_name}'", "serve")
            answers.append(f"{label}: 200 in "
                           f"{time.monotonic() - t_req:.2f}s, top "
                           f"'{top}'")
        status, raw = _http("GET", base + "/healthz")
        hz = json.loads(raw)
        check(status == 200 and hz.get("status") == "serving"
              and hz["breakers"] == {"extractor": "closed",
                                     "device": "closed"}, "serve",
              f"/healthz answered {status}: {raw[:300]!r}", "serve")
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=max(budget - (time.monotonic() - t0),
                                       5.0))
        except subprocess.TimeoutExpired:
            raise PhaseFailed("serve: no exit after SIGTERM\n"
                              + _tail(log_path))
        check(rc == 0, "serve", f"exit code {rc} after SIGTERM", "serve")
        done = require_device(log_text(), "Drain complete (clean)", "serve",
                              probe)
        compiled = len(re.findall(r"Compiling predict step for shape",
                                  log_text()))
    finally:
        _reap_group(proc)
    seconds = time.monotonic() - t0
    say(f"serve: ready in {ready_s:.1f}s on {ready['kind']} "
        f"x{ready['count']} ({ready['platform']}); "
        + "; ".join(answers)
        + f"; {compiled} predict shapes compiled; /healthz serving; "
        f"SIGTERM -> exit 0; peak device memory "
        f"{_gib(done['peak_bytes'])}; extractor: warm pool of {EXTRACTOR}; "
        f"{seconds:.1f}s")
    return {"seconds": seconds, "ready_s": ready_s,
            "peak": done["peak_bytes"], "responses": len(answers)}


# ------------------------------------------------------------------- main

def main() -> int:
    for needed in ("code2vec.py", "code2vec_tpu", "Input.java",
                   os.path.join("cpp", "Makefile")):
        if not os.path.exists(os.path.join(REPO, needed)):
            print(f"chip_smoke.py: {needed} is missing next to this script;"
                  f" run it from the root of a checkout", file=sys.stderr)
            return 2
    try:
        probe = probe_device()
        say(f"probe: platform {probe['platform']}, {probe['kind']} "
            f"x{probe['count']}")
        shutil.rmtree(WORK, ignore_errors=True)
        build_s = build_native()
        say(f"build: make -C cpp into a clean cpp/build in {build_s:.1f}s; "
            f"extractor {EXTRACTOR}; loader {LOADER}")
        t0 = time.monotonic()
        gen = generate_data()
        say(f"generate: {TOKEN_ROWS:,}/{PATH_ROWS:,}/{TARGET_ROWS:,}-row "
            f"dictionaries, {gen['train_rows']} train and "
            f"{gen['val_rows']} val rows (seed {SEED}; {gen['real_name']} "
            f"from Input.java, {gen['real_contexts']} contexts) in "
            f"{time.monotonic() - t0:.1f}s")
        train = train_phase(probe)
        restored = eval_phase("eval", probe, [])
        same_eval(restored["eval"], train["eval"],
                  "restored checkpoint vs the trainer's own evaluation",
                  "eval")
        full = eval_phase("eval_full", probe, ["--topk_block", "0"])
        same_eval(restored["eval"], full["eval"],
                  "blockwise head vs materialized logits + lax.top_k",
                  "eval_full")
        serve = serve_phase(probe, gen["real_name"])
        check(serve["responses"] >= 3, "serve", "fewer than 3 responses")
    except PhaseFailed as e:
        print(f"chip_smoke.py FAILED: {e}", file=sys.stderr)
        return 1
    say(f"four-chip phase: not run ({probe['count']} chips); the "
        f"--dp 4 / --tp 4 trainer runs are made by hand on the four-chip "
        f"machine (.claude/skills/verify/SKILL.md)")
    say(f"all phases passed in {time.monotonic() - _T0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": probe["platform"], "kind": probe["kind"],
        "count": probe["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
