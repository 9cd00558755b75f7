"""Seeded dictionaries and packed corpora at a configuration's published
row counts, written with numpy straight into the program's on-disk
formats (the `.dict.c2v` pickle of three frequency dictionaries and the
`.c2vb` header + int32 records), so that the program loads them through
its normal path. The generator follows `chip_smoke.py`'s `generate_data`
(heavy-tailed context counts) and adds what a speed measurement needs:
ids that cover the whole vocabularies with a Zipf skew, and rows by the
hundred thousand without a Python loop over rows.

A corpus is a function of the traffic file's own `corpus_seed`, not of
`--seed`: every run of a cell reads the same rows and `--seed` decides
their order (the program shuffles with it), the weights and the dropout
mask. So every seed gives the same set of sizes, in another order.
"""

from __future__ import annotations

import json
import os
import pickle
import struct
from typing import Dict, List, Optional, Sequence

import numpy as np

_HEADER = struct.Struct("<4sIII")       # magic, version, rows, contexts
_MAGIC, _VERSION = b"C2VB", 1
_SYL = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]


def _path_word(i: int) -> str:
    # odd multiplier: a bijection on 32-bit ints, like a hashed path
    return str((i * 2654435761) % (1 << 32) - (1 << 31))


def _target_word(i: int) -> str:
    # letters and '|' only: names must pass the legality filter
    a, b, c, d = i // 343000 % 70, i // 4900 % 70, i // 70 % 70, i % 70
    return f"{_SYL[a]}{_SYL[b]}|{_SYL[c]}{_SYL[d]}"


def vocabulary(head: Sequence[str], rows: int, word) -> List[str]:
    """`head` (real words, most frequent first), then word(0), word(1),
    ... up to rows - 1 words: the program adds one special word."""
    words, taken, i = list(head), set(head), 0
    if len(words) > rows - 1:
        raise ValueError(f"{len(words)} real words for {rows - 1} rows")
    while len(words) < rows - 1:
        w = word(i)
        if w not in taken:
            words.append(w)
        i += 1
    return words


def write_dictionaries(path: str, config: Dict, corpus_rows: int,
                       head: Optional[Dict[str, Sequence[str]]] = None
                       ) -> Dict[str, List[str]]:
    """The `.dict.c2v` the program builds its vocabularies from. Counts
    fall strictly, so word k gets index k + 1 (index 0 is the special
    word). Returns the three word lists in index order."""
    head = head or {}
    words = {
        "token": vocabulary(head.get("token", ()), config["token_rows"],
                            lambda i: f"tok{i}"),
        "path": vocabulary(head.get("path", ()), config["path_rows"],
                           _path_word),
        "target": vocabulary(head.get("target", ()), config["target_rows"],
                             _target_word),
    }
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        for kind in ("token", "path", "target"):
            n = len(words[kind])
            pickle.dump(dict(zip(words[kind], range(n + 1, 1, -1))), f,
                        protocol=pickle.HIGHEST_PROTOCOL)
        pickle.dump(int(corpus_rows), f)
    os.replace(tmp, path)
    return words


def zipf_ids(rng: np.random.Generator, size, rows: int, exponent: float
             ) -> np.ndarray:
    """Ids in 1..rows-1 with P(id) falling as id^-exponent: the inverse
    CDF of the continuous bounded power law, floored."""
    n = rows - 1
    u = rng.random(size)
    if abs(exponent - 1.0) < 1e-9:
        x = np.exp(u * np.log(n + 1.0))
    else:
        p = 1.0 - exponent
        x = ((float(n + 1) ** p - 1.0) * u + 1.0) ** (1.0 / p)
    return np.minimum(x.astype(np.int64), n).astype(np.int32)


def context_counts(rng: np.random.Generator, rows: int, spec: Dict,
                   cap: int) -> np.ndarray:
    """Contexts per row: log-normal as chip_smoke.py draws them, clipped
    to [min, the configuration's cap]."""
    n = rng.lognormal(spec["mu"], spec["sigma"], rows).astype(np.int64)
    return np.clip(n, spec["min"], cap).astype(np.int32)


def write_corpus(path: str, config: Dict, traffic: Dict) -> Dict:
    """A packed `.c2vb` of `traffic["corpus_rows"]` rows at the
    configuration's context cap; rows are [label, sources, paths,
    targets], padding is id 0. Returns its facts."""
    rows, cap = int(traffic["corpus_rows"]), int(config["max_contexts"])
    rng = np.random.default_rng(int(traffic["corpus_seed"]))
    s = traffic["zipf_exponent"]
    tmp = path + ".tmp"
    valid_total = 0
    with open(tmp, "wb") as f:
        f.write(_HEADER.pack(_MAGIC, _VERSION, rows, cap))
        for start in range(0, rows, 8192):
            n = min(8192, rows - start)
            counts = context_counts(rng, n, traffic["context_count"], cap)
            valid = np.arange(cap, dtype=np.int32)[None, :] < counts[:, None]
            rec = np.zeros((n, 1 + 3 * cap), np.int32)
            rec[:, 0] = zipf_ids(rng, n, config["target_rows"], s["target"])
            for k, (kind, key) in enumerate((("token", "token_rows"),
                                             ("path", "path_rows"),
                                             ("token", "token_rows"))):
                ids = zipf_ids(rng, (n, cap), config[key], s[kind])
                rec[:, 1 + k * cap:1 + (k + 1) * cap] = np.where(valid, ids, 0)
            f.write(rec.tobytes())
            valid_total += int(counts.sum())
    os.replace(tmp, path)
    facts = {"rows": rows, "max_contexts": cap,
             "mean_valid_contexts": valid_total / rows}
    with open(path + ".facts.json", "w") as f:
        json.dump(facts, f)
    return facts


def read_corpus(path: str) -> np.ndarray:
    """The records of a `.c2vb` as an (N, 1 + 3M) int32 memmap."""
    with open(path, "rb") as f:
        magic, _, n, m = _HEADER.unpack(f.read(_HEADER.size))
    if magic != _MAGIC:
        raise ValueError(f"{path} is not a .c2vb file")
    return np.memmap(path, dtype=np.int32, mode="r", offset=_HEADER.size,
                     shape=(n, 1 + 3 * m))


def row_fingerprints(rec: np.ndarray) -> np.ndarray:
    """One 64-bit fingerprint per record (label and every id, in place):
    lets a run check that the rows the program fed to the step are rows
    of the corpus, all different, without keeping the corpus in memory."""
    width = rec.shape[1]
    mult = (np.arange(1, width + 1, dtype=np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
    out = np.empty(rec.shape[0], np.uint64)
    for start in range(0, rec.shape[0], 16384):
        block = np.asarray(rec[start:start + 16384]).astype(np.uint64)
        out[start:start + 16384] = (block * mult[None, :]).sum(
            axis=1, dtype=np.uint64)
    return out


def prepare_train_data(work: str, config: Dict, traffic: Dict) -> Dict:
    """Dictionaries and corpus under `work`/data, made on the cell's
    first run and reused afterwards. Returns the `--data` prefix and the
    corpus facts."""
    data = os.path.join(work, "data")
    os.makedirs(data, exist_ok=True)
    prefix = os.path.join(data, "corpus")
    dict_path, packed = prefix + ".dict.c2v", prefix + ".train.c2vb"
    made = False
    if not os.path.exists(dict_path):
        write_dictionaries(dict_path, config, traffic["corpus_rows"])
        made = True
    if not os.path.exists(packed + ".facts.json"):
        write_corpus(packed, config, traffic)
        made = True
    with open(packed + ".facts.json") as f:
        facts = json.load(f)
    return {"prefix": prefix, "packed": packed, "made": made, **facts}
