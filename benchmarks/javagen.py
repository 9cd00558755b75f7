"""Seeded Java classes for the serve cells' requests: a copy of the
class generator of `experiments/javagen.py` (word pools, fields, the
method families and `generate_class`), kept here so that no later change
to the program's tree can move the traffic. The corpus, project and
Bayes-ceiling parts of the original are not copied; PERF.md lists the
original under Open questions.

Every class is a syntactically valid compilation unit exercising fields,
loops, conditionals, ternaries, lambdas, generics, arrays and string
building; method names are semantic functions of method bodies.
"""

from __future__ import annotations

import random
from typing import Callable, List, Sequence, Tuple

# ----------------------------------------------------------------- word pools

NOUNS = [
    "user", "account", "item", "order", "node", "edge", "token", "price",
    "event", "config", "cache", "buffer", "record", "session", "message",
    "task", "job", "key", "value", "index", "point", "shape", "color",
    "file", "path", "name", "id", "total", "limit", "offset", "score",
    "rate", "weight", "amount", "balance", "customer", "product", "entry",
    "field", "row", "column", "label", "tag", "group", "member", "owner",
    "parent", "child", "result", "status", "state", "error", "warning",
    "request", "response", "header", "body", "payload", "channel", "queue",
    "stack", "tree", "graph", "list", "chunk", "block", "page", "frame",
    "widget", "panel", "button", "window", "image", "sound", "track",
    "segment", "region", "zone", "slot", "ticket", "invoice", "payment",
]

ADJS = ["active", "valid", "pending", "cached", "remote", "local", "last",
        "first", "next", "prev", "old", "new", "raw", "final", "base",
        "temp", "hidden", "open", "closed", "dirty"]

SCALAR_TYPES = [("int", "0"), ("long", "0L"), ("double", "0.0"),
                ("float", "0.0f"), ("String", "\"\""), ("boolean", "false")]

NUM_TYPES = [("int", "0"), ("long", "0L"), ("double", "0.0")]


def cap(w: str) -> str:
    return w[:1].upper() + w[1:]


def camel(parts: Sequence[str]) -> str:
    return parts[0] + "".join(cap(p) for p in parts[1:])


def plural(w: str) -> str:
    if w.endswith("s") or w.endswith("x") or w.endswith("h"):
        return w + "es"
    if w.endswith("y"):
        return w[:-1] + "ies"
    return w + "s"


# ------------------------------------------------------------------- fields

class Field:
    """A class field the method families draw on."""

    def __init__(self, rng: random.Random, nouns: List[str]):
        self.noun = rng.choice(nouns)
        self.adj = rng.choice(ADJS) if rng.random() < 0.25 else None
        parts = ([self.adj] if self.adj else []) + [self.noun]
        self.kind = rng.choices(["scalar", "num", "list", "array", "map"],
                                weights=[30, 22, 26, 12, 10])[0]
        if self.kind == "scalar":
            self.type, self.default = rng.choice(SCALAR_TYPES)
            self.name = camel(parts)
        elif self.kind == "num":
            self.type, self.default = rng.choice(NUM_TYPES)
            self.name = camel(parts)
        elif self.kind == "list":
            self.elem, self.elem_default = rng.choice(NUM_TYPES[:1] + [("String", "\"\"")])
            boxed = {"int": "Integer", "String": "String"}[self.elem]
            self.type = f"List<{boxed}>"
            self.default = f"new ArrayList<{boxed}>()"
            self.name = camel(parts[:-1] + [plural(self.noun)])
        elif self.kind == "array":
            self.elem = rng.choice(["int", "double", "String"])[:]
            self.type = f"{self.elem}[]"
            self.default = f"new {self.elem}[8]"
            self.name = camel(parts[:-1] + [plural(self.noun)])
        else:
            self.type = "Map<String, Integer>"
            self.default = "new HashMap<String, Integer>()"
            self.name = camel(parts[:-1] + [self.noun, "map"])
        self.name_parts = parts if self.kind in ("scalar", "num") else (
            parts[:-1] + ([plural(self.noun)] if self.kind in ("list", "array")
                          else [self.noun, "map"]))

    @property
    def iterable(self) -> bool:
        return self.kind in ("list", "array")

    @property
    def numeric_elem(self) -> bool:
        return self.iterable and self.elem in ("int", "long", "double")

    @property
    def numeric(self) -> bool:
        return self.kind == "num" or (self.kind == "scalar"
                                      and self.type in ("int", "long",
                                                        "double", "float"))


# ------------------------------------------------------------ method families
#
# Each family is (weight, applicable(field), generate(field, rng) ->
# (name_parts, return_type, params, body_lines)). Verb synonym sets give
# the task its irreducible ambiguity.

def _verb(rng, choices):
    words, weights = zip(*choices)
    return rng.choices(words, weights=weights)[0]


def fam_getter(f, rng):
    if f.type == "boolean" and rng.random() < 0.7:
        name = ["is", *f.name_parts]
    else:
        name = [_verb(rng, [("get", 80), ("fetch", 10), ("read", 10)]),
                *f.name_parts]
    return name, f.type, "", [f"return this.{f.name};"]


def fam_setter(f, rng):
    v = _verb(rng, [("set", 80), ("update", 12), ("assign", 8)])
    body = [f"this.{f.name} = {f.name};"]
    if rng.random() < 0.2:
        body = [f"if ({f.name} != null) {{", f"    this.{f.name} = {f.name};",
                "}"] if not f.numeric else [
            f"if ({f.name} >= 0) {{", f"    this.{f.name} = {f.name};", "}"]
    return [v, *f.name_parts], "void", f"{f.type} {f.name}", body


def fam_with(f, rng, class_name=None):
    return (["with", *f.name_parts], class_name or "Object",
            f"{f.type} {f.name}",
            [f"this.{f.name} = {f.name};", "return this;"])


def fam_adder(f, rng):
    v = _verb(rng, [("add", 60), ("append", 20), ("push", 10), ("insert", 10)])
    elem = "Integer" if f.kind == "list" and f.elem == "int" else "String"
    if f.kind == "list":
        body = [f"this.{f.name}.add({f.noun});"]
        if rng.random() < 0.3:
            body = [f"if ({f.noun} != null) {{",
                    f"    this.{f.name}.add({f.noun});", "}"]
        return [v, f.noun], "void", f"{elem} {f.noun}", body
    return None


def fam_remover(f, rng):
    if f.kind != "list":
        return None
    v = _verb(rng, [("remove", 60), ("delete", 25), ("drop", 15)])
    return ([v, f.noun], "void", f"Object {f.noun}",
            [f"this.{f.name}.remove({f.noun});"])


def fam_clear(f, rng):
    if f.kind not in ("list", "map"):
        return None
    v = _verb(rng, [("clear", 60), ("reset", 30), ("empty", 10)])
    return [v, *f.name_parts], "void", "", [f"this.{f.name}.clear();"]


def fam_count(f, rng):
    if f.kind not in ("list", "map", "array"):
        return None
    v = _verb(rng, [("count", 50), ("size", 20), ("num", 30)])
    acc = "length" if f.kind == "array" else "size()"
    style = rng.randrange(3)
    if style == 0 or f.kind != "list":
        body = [f"return this.{f.name}.{acc};"]
    elif style == 1:
        body = ["int count = 0;",
                f"for (Object it : this.{f.name}) {{", "    count++;", "}",
                "return count;"]
    else:
        body = [f"int n = this.{f.name}.size();", "return n;"]
    return [v, *f.name_parts], "int", "", body


def fam_sum(f, rng):
    if not f.numeric_elem:
        return None
    v = _verb(rng, [("sum", 45), ("total", 35), ("aggregate", 20)])
    t = f.elem
    style = rng.randrange(2)
    if f.kind == "array" or style == 0:
        loop = (f"for ({t} v : this.{f.name}) {{", "    acc += v;", "}")
    else:
        loop = (f"for (int i = 0; i < this.{f.name}.size(); i++) {{",
                f"    acc += this.{f.name}.get(i);", "}")
    return ([v, *f.name_parts], t, "",
            [f"{t} acc = {dict(NUM_TYPES)[t]};", *loop, "return acc;"])


def fam_max(f, rng):
    if not f.numeric_elem or f.kind != "array":
        return None
    hi = rng.random() < 0.5
    v = _verb(rng, [("max", 45), ("largest", 30), ("highest", 25)] if hi
              else [("min", 45), ("smallest", 30), ("lowest", 25)])
    op = ">" if hi else "<"
    t = f.elem
    return ([v, f.noun], t, "",
            [f"{t} best = this.{f.name}[0];",
             f"for (int i = 1; i < this.{f.name}.length; i++) {{",
             f"    if (this.{f.name}[i] {op} best) {{",
             f"        best = this.{f.name}[i];", "    }", "}",
             "return best;"])


def fam_average(f, rng):
    if not f.numeric_elem or f.kind != "array":
        return None
    v = _verb(rng, [("average", 55), ("mean", 45)])
    return ([v, f.noun], "double", "",
            ["double acc = 0.0;",
             f"for ({f.elem} v : this.{f.name}) {{", "    acc += v;", "}",
             f"return acc / this.{f.name}.length;"])


def fam_contains(f, rng):
    if f.kind != "list":
        return None
    v = _verb(rng, [("contains", 50), ("has", 35), ("includes", 15)])
    style = rng.randrange(2)
    if style == 0:
        body = [f"return this.{f.name}.contains({f.noun});"]
    else:
        body = [f"for (Object it : this.{f.name}) {{",
                f"    if (it.equals({f.noun})) {{", "        return true;",
                "    }", "}", "return false;"]
    return [v, f.noun], "boolean", f"Object {f.noun}", body


def fam_index_of(f, rng):
    if f.kind != "array" or f.elem == "double":
        return None
    v = _verb(rng, [("indexOf", 40), ("find", 35), ("locate", 25)])
    name = [v, f.noun] if v == "indexOf" else [v, f.noun, "index"]
    eq = (f"this.{f.name}[i] == {f.noun}" if f.elem == "int"
          else f"this.{f.name}[i].equals({f.noun})")
    return (name, "int", f"{f.elem} {f.noun}",
            [f"for (int i = 0; i < this.{f.name}.length; i++) {{",
             f"    if ({eq}) {{", "        return i;", "    }", "}",
             "return -1;"])


def fam_is_empty(f, rng):
    if f.kind not in ("list", "map"):
        return None
    neg = rng.random() < 0.3
    if neg:
        return (["has", *f.name_parts], "boolean", "",
                [f"return !this.{f.name}.isEmpty();"])
    return (["is", *f.name_parts, "empty"], "boolean", "",
            [f"return this.{f.name}.isEmpty();"])


def fam_describe(f, rng):
    v = _verb(rng, [("describe", 30), ("format", 40), ("render", 30)])
    if f.kind == "list":
        body = ["StringBuilder sb = new StringBuilder();",
                f"for (Object it : this.{f.name}) {{",
                "    sb.append(it).append(',');", "}",
                "return sb.toString();"]
    else:
        body = [f"return \"{f.name}=\" + this.{f.name};"]
    return [v, *f.name_parts], "String", "", body


def fam_parse(f, rng):
    if not (f.kind in ("scalar", "num") and f.type in ("int", "long", "double")):
        return None
    v = _verb(rng, [("parse", 60), ("decode", 25), ("extract", 15)])
    conv = {"int": "Integer.parseInt", "long": "Long.parseLong",
            "double": "Double.parseDouble"}[f.type]
    return ([v, *f.name_parts], f.type, "String text",
            [f"this.{f.name} = {conv}(text.trim());",
             f"return this.{f.name};"])


def fam_validate(f, rng):
    v = _verb(rng, [("validate", 45), ("check", 35), ("verify", 20)])
    if f.numeric:
        cond = f"this.{f.name} < 0"
    elif f.type == "String":
        cond = f"this.{f.name} == null || this.{f.name}.isEmpty()"
    elif f.kind in ("list", "map"):
        cond = f"this.{f.name} == null"
    else:
        return None
    return ([v, *f.name_parts], "void", "",
            [f"if ({cond}) {{",
             f"    throw new IllegalStateException(\"bad {f.name}\");",
             "}"])


def fam_copy(f, rng):
    if f.kind != "list":
        return None
    v = _verb(rng, [("copy", 55), ("clone", 20), ("snapshot", 25)])
    return ([v, *f.name_parts], f.type, "",
            [f"return new ArrayList<>(this.{f.name});"])


def fam_reverse(f, rng):
    if f.kind != "array":
        return None
    return (["reverse", *f.name_parts], "void", "",
            [f"for (int i = 0; i < this.{f.name}.length / 2; i++) {{",
             f"    {f.elem} tmp = this.{f.name}[i];",
             f"    this.{f.name}[i] = this.{f.name}[this.{f.name}.length - 1 - i];",
             f"    this.{f.name}[this.{f.name}.length - 1 - i] = tmp;", "}"])


def fam_increment(f, rng):
    if not (f.kind == "num" and f.type in ("int", "long")):
        return None
    v = _verb(rng, [("increment", 40), ("bump", 25), ("advance", 35)])
    style = rng.randrange(3)
    body = {0: [f"this.{f.name}++;"],
            1: [f"this.{f.name} += 1;"],
            2: [f"this.{f.name} = this.{f.name} + 1;"]}[style]
    return [v, *f.name_parts], "void", "", body


def fam_scale(f, rng):
    if not (f.kind == "num" and f.type == "double"):
        return None
    v = _verb(rng, [("scale", 45), ("multiply", 30), ("apply", 25)])
    return ([v, *f.name_parts], "void", "double factor",
            [f"this.{f.name} *= factor;"])


def fam_filter(f, rng):
    if not (f.kind == "list" and f.elem == "int"):
        return None
    v = _verb(rng, [("filter", 45), ("select", 35), ("pick", 20)])
    adj = rng.choice(["positive", "large", "small", "even"])
    cond = {"positive": "v > 0", "large": "v > 100", "small": "v < 10",
            "even": "v % 2 == 0"}[adj]
    return ([v, adj, *f.name_parts], f.type, "",
            ["List<Integer> out = new ArrayList<>();",
             f"for (int v : this.{f.name}) {{",
             f"    if ({cond}) {{", "        out.add(v);", "    }", "}",
             "return out;"])


def fam_lookup(f, rng):
    if f.kind != "map":
        return None
    v = _verb(rng, [("lookup", 40), ("resolve", 30), ("get", 30)])
    return ([v, f.noun], "Integer", "String key",
            [f"Integer v = this.{f.name}.get(key);",
             "return v == null ? 0 : v;"] if rng.random() < 0.5 else
            [f"return this.{f.name}.getOrDefault(key, 0);"])


def fam_store(f, rng):
    if f.kind != "map":
        return None
    v = _verb(rng, [("store", 40), ("put", 35), ("register", 25)])
    return ([v, f.noun], "void", "String key, int value",
            [f"this.{f.name}.put(key, value);"])


FAMILIES: List[Tuple[int, Callable]] = [
    (22, fam_getter), (16, fam_setter), (3, fam_with), (6, fam_adder),
    (4, fam_remover), (3, fam_clear), (5, fam_count), (5, fam_sum),
    (4, fam_max), (2, fam_average), (5, fam_contains), (4, fam_index_of),
    (3, fam_is_empty), (4, fam_describe), (3, fam_parse), (4, fam_validate),
    (2, fam_copy), (2, fam_reverse), (3, fam_increment), (2, fam_scale),
    (3, fam_filter), (3, fam_lookup), (2, fam_store),
]

NOISE_LINES = [
    "System.out.println(\"debug\");",
    "// TODO revisit",
    "long start = System.nanoTime();",
]


def expand_nouns(ident_scale: int, seed: int = 5) -> List[str]:
    """Deterministically expand the 80-noun base pool to ~80*ident_scale
    single-word nouns by compounding base words (userProfile-style
    identifiers, lowercased to one subtoken). This is the identifier-space
    lever for flagship-shape vocab studies: token/target vocab sizes are
    driven by how many distinct identifier spellings exist in the corpus,
    not by how many files are generated. The family/verb machinery — and
    therefore the Bayes ceiling — is untouched: which family/verb is
    drawn never depends on the noun spelling."""
    if ident_scale <= 1:
        return list(NOUNS)
    rng = random.Random(seed)
    pool = list(NOUNS)
    seen = set(pool)
    target = 80 * ident_scale
    misses = 0
    while len(pool) < target:
        a, b = rng.choice(NOUNS), rng.choice(NOUNS)
        if a == b:
            continue
        w = a + b
        # Two-noun compounds top out at ~82*81; past ~60% occupancy the
        # rejection rate climbs, so widen to triples instead of crawling
        # (and at very large targets, hanging) on pair collisions.
        if w in seen:
            misses += 1
            if misses > 8:
                w = a + b + rng.choice(NOUNS)
        if w not in seen:
            seen.add(w)
            pool.append(w)
            misses = 0
    return pool


# ----------------------------------------------------------------- rendering

def _render_method(name_parts, ret, params, body, rng,
                   literal_pool=None, literal_rate=0.0) -> List[str]:
    name = camel(name_parts)
    mods = rng.choices(["public ", "", "protected ", "public static "],
                       weights=[70, 15, 10, 5])[0]
    if "this." in " ".join(body):
        mods = mods.replace("static ", "")
    lines = [f"    {mods}{ret} {name}({params}) {{"]
    if rng.random() < 0.08:
        lines.append("        " + rng.choice(NOISE_LINES))
    if literal_pool and rng.random() < literal_rate:
        # Distinct-ish log-message literals: real corpora carry a long
        # tail of string-literal leaf tokens (java14m's 1.3M token vocab
        # is mostly such a tail); each 3-word draw from a large pool is
        # a new spelling w.h.p., so literal_rate directly dials how many
        # distinct token-vocab rows the corpus produces.
        words = " ".join(rng.choice(literal_pool) for _ in range(3))
        lines.append(f'        System.out.println("{words}");')
    for b in body:
        lines.append("        " + b)
    lines.append("    }")
    return lines


def generate_class(rng: random.Random, nouns: List[str], class_name: str,
                   package: str, n_methods: int,
                   literal_pool=None, literal_rate=0.0) -> str:
    fields = [Field(rng, nouns) for _ in range(rng.randint(3, 8))]
    lines = [f"package {package};", "",
             "import java.util.*;", ""]
    if rng.random() < 0.15:
        lines += ["import java.util.function.*;", ""]
    lines.append(f"public class {class_name} {{")
    for f in fields:
        init = f" = {f.default}" if rng.random() < 0.6 else ""
        mod = rng.choice(["private ", "private ", "private final ", ""])
        if "final" in mod and not init:
            init = f" = {f.default}"
        lines.append(f"    {mod}{f.type} {f.name}{init};")
    lines.append("")

    made = set()
    weights = [w for w, _ in FAMILIES]
    fams = [g for _, g in FAMILIES]
    tries = 0
    count = 0
    while count < n_methods and tries < n_methods * 12:
        tries += 1
        fam = rng.choices(fams, weights=weights)[0]
        f = rng.choice(fields)
        out = (fam(f, rng, class_name) if fam is fam_with else fam(f, rng))
        if out is None:
            continue
        name_parts, ret, params, body = out
        name = camel(name_parts)
        if name in made:
            continue
        made.add(name)
        lines.extend(_render_method(name_parts, ret, params, body, rng,
                                    literal_pool=literal_pool,
                                    literal_rate=literal_rate))
        lines.append("")
        count += 1

    # occasional parser-stress extras (lambdas, nested enum)
    if rng.random() < 0.10:
        lines += ["    private Runnable task = () -> {",
                  "        System.out.println(\"run\");", "    };", ""]
    if rng.random() < 0.05:
        lines += ["    enum Mode { FAST, SLOW, AUTO }", ""]
    lines.append("}")
    return "\n".join(lines) + "\n"
