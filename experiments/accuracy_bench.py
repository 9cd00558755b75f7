"""End-to-end accuracy harness: generated-corpus method-name prediction.

Runs the COMPLETE production pipeline — native C++ extractor
(cpp/c2v-extract) -> offline preprocess (histograms, in-vocab-preferring
context sampling, dict pickling) -> vocab build -> packed-data training
-> per-epoch evaluation (top-1/5/10 accuracy + subtoken precision/
recall/F1, the reference's metric definitions,
tensorflow_model.py:449-512) — on the generated realistic Java corpus
(experiments/javagen.py), with train/val/test split by project.

Writes `experiments/results/accuracy.json` (convergence curve + final
test metrics) and refreshes `BENCH_ACCURACY.md` at the repo root.

Usage:
    python experiments/accuracy_bench.py [--root DIR] [--epochs N]
        [--fresh]

Runs on the device JAX resolves (JAX_PLATFORMS=cpu for a CPU run); the
facade's "Model created" log line names it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from experiments import javagen  # noqa: E402


def build_dataset(root: str, language: str = "java", scale: int = 1,
                  ident_scale: int = 1, literal_rate: float = 0.0,
                  log=print) -> str:
    """Generate + extract + preprocess; returns the dataset prefix.
    language="cs" routes through the C# generator (experiments/csgen.py)
    and the native C# extractor (cpp/c2v-extract-cs) — BASELINE config #3.
    scale multiplies the generated file counts (data-scaling studies);
    ident_scale/literal_rate widen the identifier space
    (javagen.expand_nouns) for flagship-shape vocab runs.
    """
    from code2vec_tpu.data.preprocess import extract_dir, preprocess

    corpus = os.path.join(root, "src")
    log(f"Generating {language} corpus (scale {scale}, "
        f"ident_scale {ident_scale}, literal_rate {literal_rate})...")
    sizes = dict(train_files=2400 * scale, val_files=260 * scale,
                 test_files=260 * scale)
    if language == "cs":
        if ident_scale != 1 or literal_rate:
            raise SystemExit("ident_scale/literal_rate are implemented for "
                             "the Java generator only")
        from experiments import csgen
        dirs = csgen.generate_corpus(corpus, log=log, **sizes)
    else:
        dirs = javagen.generate_corpus(corpus, log=log, ident_scale=ident_scale,
                                       literal_rate=literal_rate, **sizes)
    raws = {}
    for role in ("train", "val", "test"):
        raws[role] = extract_dir(
            dirs[role], os.path.join(root, f"{role}.raw.txt"),
            language=language, num_threads=16, shuffle=(role == "train"),
            num_workers=min(4, os.cpu_count() or 1))
    prefix = os.path.join(root, _prefix_name(language))
    # .train.c2v must pair with "val" for mid-training eval, as the
    # reference trains with --test pointed at the val split (train.sh:13).
    preprocess(raws["train"], raws["val"], raws["test"], prefix,
               max_contexts=200, log=log)
    return prefix


def _prefix_name(language: str) -> str:
    return "gencs" if language == "cs" else "genjava"


def _resume_checkpoint(save_base: str, epochs_evaluated: int):
    """Newest `_iter<N>[_preempt]` artifact with N <= epochs_evaluated —
    i.e. the last EVALUATED epoch. A run can die between the end-of-epoch
    save and the eval record (e.g. a wedged device transfer during the
    eval), leaving a checkpoint one epoch ahead of the curve; resuming
    from it would desynchronize curve indexing, so that orphan epoch is
    retrained instead. At equal N the preemption artifact wins (it is
    strictly more trained, mid-epoch N+1)."""
    import glob as _glob
    from code2vec_tpu.training.checkpoint import parse_iter_name
    best = None  # ((epoch, is_preempt), path)
    for p in _glob.glob(save_base + "_iter*"):
        parsed = parse_iter_name(p)
        if parsed is None or parsed[0] > epochs_evaluated:
            continue
        if best is None or parsed > best[0]:
            best = (parsed, p)
    return best[1] if best else None


def target_oov_rate(c2v_path: str, target_vocab) -> float:
    """Fraction of a split's examples whose exact target name is absent
    from the training target vocabulary. Because the split is by project
    (partially disjoint identifier vocabularies), some val/test names are
    unpredictable-by-construction; the corpus Bayes ceiling must be read
    net of this rate."""
    total = oov = 0
    with open(c2v_path) as f:
        for line in f:
            name = line.split(" ", 1)[0]
            total += 1
            if target_vocab.lookup_index(name) == target_vocab.oov_index:
                oov += 1
    return oov / max(total, 1)


def run(root: str, epochs: int, patience: int, language: str = "java",
        scale: int = 1, ident_scale: int = 1, literal_rate: float = 0.0,
        sparse: bool = False, rss_limit_gb: float = 100.0,
        resume: bool = False, log=print) -> dict:
    import jax
    import numpy as np
    from code2vec_tpu.config import Config
    from code2vec_tpu.model_facade import Code2VecModel
    from code2vec_tpu.training.loop import Trainer
    from code2vec_tpu.training.state import dropout_rng

    prefix = os.path.join(root, _prefix_name(language))
    scale_marker = prefix + ".scale"
    shape = {"scale": scale, "ident_scale": ident_scale,
             "literal_rate": literal_rate}
    if not os.path.exists(prefix + ".train.c2v"):
        prefix = build_dataset(root, language=language, scale=scale,
                               ident_scale=ident_scale,
                               literal_rate=literal_rate, log=log)
        with open(scale_marker, "w") as f:
            json.dump(shape, f)
    else:
        cached = {"scale": 1, "ident_scale": 1, "literal_rate": 0.0}
        if os.path.exists(scale_marker):
            raw = open(scale_marker).read()
            try:
                cached.update(json.loads(raw))
            except json.JSONDecodeError:   # pre-round-5 plain-int marker
                cached["scale"] = int(raw)
        if cached != shape:
            raise SystemExit(
                f"cached corpus at {root} was built with {cached}, "
                f"requested {shape}: use --fresh or a different "
                f"--root so artifacts are never mislabeled")

    # The ceiling is language-independent: csgen translates javagen's
    # family output surface-syntactically, never changing which family,
    # field, style or verb was drawn, so P(name | observable code) — and
    # therefore the Bayes-optimal scores — are identical (csgen.py doc).
    log("Computing Bayes ceiling (javagen.family_ceiling)...")
    ceiling = javagen.family_ceiling(log=log)

    save_base = os.path.join(root, "model", _prefix_name(language))
    # Phase-resume support: a long run may be cut short by the RSS
    # watchdog (note below). Each phase trains until the
    # watchdog (or the epoch budget / patience) stops it; phase state
    # (curve + best-so-far + patience counter) persists here and the next
    # `--resume` invocation continues from the newest checkpoint with a
    # fresh process.
    phase_state_path = os.path.join(
        root, f"phase_state{'_sparse' if sparse else ''}.json")
    phase = {"curve": [], "best_f1": -1.0, "best_epoch": 0, "since": 0,
             "wall_s": 0.0, "n_phases": 1}
    load_path = None
    if resume:
        # phase_state only exists once an epoch completed; a run can trip
        # the watchdog mid-epoch-1 and leave just an _iter0_preempt
        # checkpoint, which must still be picked up.
        if os.path.exists(phase_state_path):
            with open(phase_state_path) as f:
                phase.update(json.load(f))
        phase["n_phases"] = phase.get("n_phases", 1) + 1
        load_path = _resume_checkpoint(save_base, len(phase["curve"]))
        if load_path is None:
            raise SystemExit(f"--resume: no checkpoint under {save_base} "
                             f"at or before evaluated epoch "
                             f"{len(phase['curve'])}")
        log(f"Resuming phase {phase['n_phases']}: {len(phase['curve'])} "
            f"epochs recorded, best F1 {phase['best_f1']:.4f} @ epoch "
            f"{phase['best_epoch']}, loading {load_path}")

    config = Config(
        train_data_path_prefix=prefix,
        test_data_path=prefix + ".val.c2v",
        model_save_path=save_base,
        model_load_path=load_path,
        num_train_epochs=epochs,
        # one val point (and checkpoint) per epoch: the convergence curve
        # is the artifact this harness exists to produce. Mid-epoch evals
        # off — they would corrupt patience counting and the per-epoch
        # numbering of val_curve.
        save_every_epochs=1,
        num_train_batches_to_evaluate=0,
        train_batch_size=1024,
        test_batch_size=1024,
        max_contexts=200,
        # pod-scale optimizer config (lazy touched-rows Adam for the
        # embedding tables, training/sparse_adam.py): same accuracy
        # contract as dense, proven here end to end rather than only by
        # the unit-level touched-row parity tests.
        use_sparse_embedding_update=sparse,
        # Host-memory watchdog: a long scale run checkpoints and stops
        # cleanly at this bound instead of dying to the OOM killer.
        # A tripped run is recorded as rss_preempted in the artifact
        # and never rewrites the report (truncated != converged).
        rss_limit_gb=rss_limit_gb,
    )
    model = Code2VecModel(config)

    curve = phase["curve"]
    prior_epochs = model.initial_epoch
    if resume and len(curve) != prior_epochs:
        raise SystemExit(
            f"phase state records {len(curve)} evaluated epochs but the "
            f"loaded checkpoint is at epoch {prior_epochs}; the model dir "
            f"and {phase_state_path} are out of sync")
    t0 = time.time()
    # Best-by-val-F1 params, the reference's "train past the best epoch,
    # keep the best checkpoint" workflow (README.md:87-88). In-RAM copy
    # for the common case; when the best epoch belongs to an earlier
    # phase, its `_iter<N>` checkpoint is loaded for the test eval
    # instead (max_to_keep=10 keeps it alive for any patience <= 9).
    best = {"f1": phase["best_f1"], "params": None,
            "epoch": phase["best_epoch"], "since": phase["since"]}

    base_wall = phase["wall_s"]  # completed earlier phases' wall time

    def eval_and_record(state):
        results = model._evaluate_with_params(state.params)
        wall = round(base_wall + time.time() - t0, 1)
        curve.append(_metrics_dict(results, wall_s=wall))
        f1 = float(results.subtoken_f1)
        if f1 > best["f1"]:
            best.update(f1=f1, params=jax.device_get(state.params),
                        epoch=len(curve), since=0)
        else:
            best["since"] += 1
        phase.update(curve=curve, best_f1=best["f1"],
                     best_epoch=best["epoch"], since=best["since"],
                     wall_s=wall)
        with open(phase_state_path, "w") as f:
            json.dump(phase, f)
        return results

    def should_stop():
        return patience > 0 and best["since"] >= patience

    # The reference evaluates against the val split during training
    # (train.sh:13-18); final test-split evaluation happens once below.
    train_step = model.builder.make_train_step(model.state)
    batches = model._train_batches()
    trainer = Trainer(config, train_step, mesh=model.mesh,
                      evaluate_fn=eval_and_record,
                      save_fn=model._make_save_fn() if config.is_saving else None,
                      initial_epoch=model.initial_epoch,
                      steps_per_epoch_hint=model._steps_per_epoch,
                      stop_fn=should_stop)
    model.state = trainer.train(model.state, batches, dropout_rng(config))

    val_best = max(curve, key=lambda r: r["f1"]) if curve else None

    # Test-split evaluation uses the best-by-val-F1 params — the honest
    # pairing (same weights for both numbers), fixing the round-2 flaw of
    # comparing an undertrained val point against a later-epoch test run.
    test_params = (best["params"] if best["params"] is not None
                   else model.state.params)
    if best["params"] is None and best["epoch"] > 0 and not trainer.preempted:
        # best epoch belongs to an earlier phase: restore its checkpoint
        from code2vec_tpu.training import checkpoint as ckpt_mod
        path = f"{save_base}_iter{best['epoch']}"
        if os.path.isdir(path):
            log(f"Loading best-by-val-F1 weights from {path}")
            test_params = ckpt_mod.load_model(
                path, model.state, params_only=True).params
        else:
            log(f"WARNING: best checkpoint {path} rotated away; "
                f"test eval uses final weights")
    model.config.test_data_path = prefix + ".test.c2v"
    model.config.num_test_examples = model._count_examples(
        model.config.test_data_path)
    test = model._evaluate_with_params(test_params)

    oov = {role: target_oov_rate(f"{prefix}.{role}.c2v",
                                 model.vocabs.target_vocab)
           for role in ("val", "test")}

    out = {
        "language": language,
        # True when the run was truncated by the host-memory watchdog
        # (or SIGTERM): such an artifact is an undertrained point and
        # must never be presented as a converged one.
        "rss_preempted": bool(trainer.preempted),
        "optimizer": {"adam_mu_dtype": config.adam_mu_dtype,
                      "adam_nu_dtype": config.adam_nu_dtype,
                      "sparse_embedding_update": sparse},
        "dataset": {
            "train_examples": config.num_train_examples,
            "val_examples": int(np.loadtxt(prefix + ".val.c2v.num_examples"))
            if os.path.exists(prefix + ".val.c2v.num_examples") else None,
            "test_examples": model.config.num_test_examples,
            "token_vocab": model.vocabs.token_vocab.size,
            "path_vocab": model.vocabs.path_vocab.size,
            "target_vocab": model.vocabs.target_vocab.size,
        },
        "epochs": epochs,
        "epochs_trained": trainer.final_epoch,
        "best_epoch": best["epoch"],
        "patience": patience,
        "train_wall_s": round(base_wall + time.time() - t0, 1),
        "phases": phase.get("n_phases", 1),
        "target_oov_rate": oov,
        "ceiling": ceiling,
        "val_curve": curve,
        "val_best": val_best,
        "test": _metrics_dict(test),
    }
    return out


def _metrics_dict(results, **extra) -> dict:
    d = dict(extra)
    d.update(
        top1=float(results.topk_acc[0]), top5=float(results.topk_acc[4]),
        top10=float(results.topk_acc[9]),
        precision=float(results.subtoken_precision),
        recall=float(results.subtoken_recall),
        f1=float(results.subtoken_f1))
    return d


def write_report(results: dict, path: str) -> None:
    t = results["test"]
    d = results["dataset"]
    c = results["ceiling"]
    oov = results["target_oov_rate"]
    vb = results["val_best"] or {}
    lines = [
        "# BENCH_ACCURACY: end-to-end learning on a realistic generated Java corpus",
        "",
        "North star: java14m subtoken F1 ≈ 59 (BASELINE.md). The build",
        "environment has no network egress and no local OSS Java trees, so this",
        "harness proves the *pipeline* learns real method-name prediction on a",
        "generated corpus engineered to have the task's actual statistical",
        "structure (experiments/javagen.py): names are semantic functions of",
        "bodies; per-family verb synonyms (get/fetch/read, sum/total/aggregate,",
        "...) put the Bayes-optimal exact-match accuracy well below 100%;",
        "train/val/test are split by project with partially disjoint identifier",
        "vocabularies, so val/test measure generalization, not memorization.",
        "",
        "Every production component is exercised end to end: the native C++",
        "extractor (cpp/c2v-extract), offline preprocessing with in-vocab",
        "context sampling (data/preprocess.py), vocab construction, the packed",
        "binary data path, the jitted train step, and the reference-definition",
        "evaluation metrics (evaluation/metrics.py; tensorflow_model.py:449-512).",
        "",
        "## Dataset",
        "",
        f"| examples (train/val/test) | {d['train_examples']} / "
        f"{d['val_examples']} / {d['test_examples']} |",
        "|---|---|",
        f"| token vocab | {d['token_vocab']} |",
        f"| path vocab | {d['path_vocab']} |",
        f"| target vocab | {d['target_vocab']} |",
        "",
        "## Bayes ceiling (what a perfect predictor could score)",
        "",
        "The per-family verb synonyms make the task irreducibly ambiguous;",
        "`javagen.family_ceiling` computes the Bayes-optimal scores by",
        "conditional resampling of the generator itself (group draws by",
        "identical observable code, read the name distribution off each",
        "group, take the optimal prediction — exact enumeration, not a",
        "heuristic; see the method comment in experiments/javagen.py).",
        "",
        "| ceiling metric | value |",
        "|---|---|",
        f"| exact match (top-1) | {c['exact_match']:.4f} |",
        f"| top-5 | {c['top5']:.4f} |",
        f"| subtoken F1 (micro) | {c['subtoken_f1_micro']:.4f} |",
        "",
        "The ceiling assumes an unrestricted predictor. A trained model can",
        "only emit names from the *train* target vocabulary, and the split",
        "is by project, so some val/test names are out-of-vocabulary by",
        f"construction: measured target-OOV rate {oov['val']:.3f} (val) / "
        f"{oov['test']:.3f} (test).",
        "The effective exact-match ceiling on the test split is therefore",
        f"≈ {(1 - oov['test']) * c['exact_match']:.4f}.",
        "",
        "## Results",
        "",
        f"Trained {results['epochs_trained']} epochs (budget "
        f"{results['epochs']}, early stop patience {results['patience']}, "
        f"{results['train_wall_s']}s wall incl. per-epoch eval). Test",
        f"metrics use the **best-by-val-F1** weights (epoch "
        f"{results['best_epoch']}) — the same weights as the val-best row,",
        "so the two numbers are directly comparable:",
        "",
        "| metric | test | val best | ceiling | test/ceiling |",
        "|---|---|---|---|---|",
        f"| top-1 accuracy | {t['top1']:.4f} | {vb.get('top1', 0):.4f} | "
        f"{(1 - oov['test']) * c['exact_match']:.4f} | "
        f"{t['top1'] / max((1 - oov['test']) * c['exact_match'], 1e-9):.1%} |",
        f"| top-5 accuracy | {t['top5']:.4f} | {vb.get('top5', 0):.4f} | "
        f"{(1 - oov['test']) * c['top5']:.4f} | "
        f"{t['top5'] / max((1 - oov['test']) * c['top5'], 1e-9):.1%} |",
        f"| subtoken precision | {t['precision']:.4f} | "
        f"{vb.get('precision', 0):.4f} | — | — |",
        f"| subtoken recall | {t['recall']:.4f} | {vb.get('recall', 0):.4f} "
        f"| — | — |",
        f"| **subtoken F1** | **{t['f1']:.4f}** | {vb.get('f1', 0):.4f} | "
        f"{c['subtoken_f1_micro']:.4f} | "
        f"{t['f1'] / c['subtoken_f1_micro']:.1%} |",
        "",
        "(The F1 ceiling is not OOV-adjusted: subtokens of an OOV name are",
        "often still predictable via an in-vocab name, so the unadjusted",
        "ceiling is the conservative denominator.)",
        "",
        "Validation convergence (one eval per actual data pass):",
        "",
        "| epoch | top-1 | top-5 | F1 |",
        "|---|---|---|---|",
    ]
    for i, r in enumerate(results["val_curve"], 1):
        lines.append(f"| {i} | {r['top1']:.4f} | {r['top5']:.4f} | "
                     f"{r['f1']:.4f} |")
    lines += [
        "",
        "## Reading the numbers against java14m F1≈59",
        "",
        "- The top-5/top-1 gap is the verb-synonym ambiguity by design: the",
        "  model's top-k ranks the synonyms (`sumPrices`, `totalPrices`, ...)",
        "  and exact-match credit goes only to the sampled one. Real corpora",
        "  have the same property — java14m's F1≈59 reflects irreducible",
        "  naming entropy, not model failure (POPL'19 §6). Here that",
        "  entropy is *known*: the ceiling table above is the corpus's",
        "  measurable analog of java14m's unknown naming entropy.",
        "- Test metrics on held-out projects (disjoint identifier",
        "  distributions) measure generalization, not memorization — the",
        "  claim java14m's F1≈59 makes on its held-out projects. Both test",
        "  and val-best come from the same weights, so their gap is the",
        "  project-shift cost, not a training-stage artifact.",
        "",
        "Raw numbers: `experiments/results/accuracy.json`. Reproduce with",
        "`python experiments/accuracy_bench.py --fresh` (deterministic seed).",
        "",
    ]
    # keep hand-curated / other-run sections intact: the data-scaling
    # summary and the C# section survive a scale-1 Java rewrite
    kept = ""
    if os.path.exists(path):
        with open(path) as f:
            existing = f.read()
        starts = [existing.index(m) for m in (_SCALE_MARKER, _CS_MARKER)
                  if m in existing]
        if starts:
            # slice from the EARLIEST marker so no kept section is lost
            kept = "\n" + existing[min(starts):]
    with open(path, "w") as f:
        f.write("\n".join(lines) + kept)


_CS_MARKER = "## C# end-to-end (BASELINE config #3)"
_SCALE_MARKER = "## Data scaling: approaching the ceiling"


def append_cs_section(results: dict, path: str) -> None:
    """Append (or replace) the C# section of BENCH_ACCURACY.md."""
    t = results["test"]
    d = results["dataset"]
    c = results["ceiling"]
    oov = results["target_oov_rate"]
    vb = results["val_best"] or {}
    eff_top1 = (1 - oov["test"]) * c["exact_match"]
    section = [
        _CS_MARKER,
        "",
        "Same harness, C# end to end: generated C# corpus",
        "(experiments/csgen.py — javagen's families rendered in C#, so the",
        "same Bayes ceiling applies; since round 5 the describe family",
        "renders as an interpolated string, so the extractor's",
        "InterpolatedStringExpression path is exercised corpus-wide) ->",
        "native C# extractor (cpp/c2v-extract-cs; reference:",
        "CSharpExtractor/Extractor/Extractor.cs:46-99) -> preprocess ->",
        "train -> eval.",
        "",
        f"Dataset: {d['train_examples']} / {d['val_examples']} / "
        f"{d['test_examples']} examples (train/val/test), target vocab "
        f"{d['target_vocab']}; target-OOV rate {oov['val']:.3f} (val) / "
        f"{oov['test']:.3f} (test).",
        "",
        f"Trained {results['epochs_trained']} epochs (budget "
        f"{results['epochs']}, patience {results['patience']}); test uses "
        f"best-by-val-F1 weights (epoch {results['best_epoch']}).",
        "",
        "| metric | test | val best | ceiling | test/ceiling |",
        "|---|---|---|---|---|",
        f"| top-1 accuracy | {t['top1']:.4f} | {vb.get('top1', 0):.4f} | "
        f"{eff_top1:.4f} | {t['top1'] / max(eff_top1, 1e-9):.1%} |",
        f"| **subtoken F1** | **{t['f1']:.4f}** | {vb.get('f1', 0):.4f} | "
        f"{c['subtoken_f1_micro']:.4f} | "
        f"{t['f1'] / c['subtoken_f1_micro']:.1%} |",
        "",
        "Raw numbers: `experiments/results/accuracy_cs.json`.",
        "",
    ]
    existing = tail = ""
    if os.path.exists(path):
        with open(path) as f:
            existing = f.read()
        if _CS_MARKER in existing:
            start = existing.index(_CS_MARKER)
            # preserve hand-curated sections after the C# one (e.g. the
            # sparse-Adam section): the old C# section ends at the next
            # "## " heading
            rest = existing[start + len(_CS_MARKER):]
            nxt = rest.find("\n## ")
            if nxt != -1:
                tail = rest[nxt + 1:]
            existing = existing[:start].rstrip() + "\n"
    body = existing.rstrip() + "\n\n" + "\n".join(section)
    if tail:
        body = body.rstrip() + "\n\n" + tail
    with open(path, "w") as f:
        f.write(body)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=None,
                   help="default: /tmp/genjava_bench or /tmp/gencs_bench")
    p.add_argument("--language", choices=["java", "cs"], default="java")
    p.add_argument("--epochs", type=int, default=12)
    p.add_argument("--patience", type=int, default=3,
                   help="early stop after this many epochs without val-F1 "
                        "improvement (0 disables); reference README.md:87-88")
    p.add_argument("--scale", type=int, default=1,
                   help="multiply generated corpus size (data-scaling runs; "
                        "results go to accuracy_scale<N>.json, the main "
                        "report is left alone)")
    p.add_argument("--ident_scale", type=int, default=1,
                   help="widen the generator's identifier space "
                        "(javagen.expand_nouns): ~80*N nouns; flagship-"
                        "shape vocab runs")
    p.add_argument("--literal_rate", type=float, default=0.0,
                   help="probability of a distinct string-literal log line "
                        "per method (drives token-vocab size like real "
                        "corpora's literal tail)")
    p.add_argument("--tag", default=None,
                   help="artifact name override: results go to "
                        "accuracy_<tag>.json and never rewrite the main "
                        "report")
    p.add_argument("--resume", action="store_true",
                   help="continue a previous (watchdog-truncated) run of "
                        "the same root from its newest checkpoint; exit "
                        "code 3 means 'truncated again, resume once more'")
    p.add_argument("--fresh", action="store_true",
                   help="regenerate the corpus from scratch")
    p.add_argument("--sparse_embedding_update", action="store_true",
                   help="train with the pod-scale lazy (touched-rows) Adam "
                        "for the embedding tables; results go to "
                        "accuracy[_...]_sparse.json, the main report is "
                        "left alone")
    p.add_argument("--rss_limit_gb", type=float, default=100.0,
                   help="checkpoint-and-stop when host RSS crosses this "
                        "(a tripped run is marked rss_preempted and never "
                        "rewrites the report); 0 disables")
    args = p.parse_args(argv)

    if args.root is None:
        suffix = f"_scale{args.scale}" if args.scale != 1 else ""
        args.root = f"/tmp/{_prefix_name(args.language)}_bench{suffix}"

    if args.fresh and os.path.exists(args.root):
        import shutil
        shutil.rmtree(args.root)
    os.makedirs(args.root, exist_ok=True)

    results = run(args.root, args.epochs, args.patience,
                  language=args.language, scale=args.scale,
                  ident_scale=args.ident_scale,
                  literal_rate=args.literal_rate,
                  sparse=args.sparse_embedding_update,
                  rss_limit_gb=args.rss_limit_gb,
                  resume=args.resume)
    results["scale"] = args.scale
    results["ident_scale"] = args.ident_scale
    results["literal_rate"] = args.literal_rate
    os.makedirs(os.path.join(REPO, "experiments", "results"), exist_ok=True)
    name = "accuracy_cs.json" if args.language == "cs" else "accuracy.json"
    if args.scale != 1:
        lang = "_cs" if args.language == "cs" else ""
        name = f"accuracy{lang}_scale{args.scale}.json"
    if args.sparse_embedding_update:
        name = name.replace(".json", "_sparse.json")
    if args.tag:
        name = f"accuracy_{args.tag}.json"
    out_json = os.path.join(REPO, "experiments", "results", name)
    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    report = os.path.join(REPO, "BENCH_ACCURACY.md")
    if results["rss_preempted"]:
        # truncated run: json (with its marker) only — an undertrained
        # point must never rewrite the report as if converged
        print("WARNING: run truncated by the host-memory watchdog; "
              "report not rewritten (exit 3: relaunch with --resume)",
              file=sys.stderr)
    elif args.scale != 1 or args.sparse_embedding_update or args.tag:
        pass  # scaling/sparse/tagged runs: json artifact only;
        #       summarized by hand
    elif args.language == "cs":
        append_cs_section(results, report)
    else:
        write_report(results, report)
    print(json.dumps({"language": args.language,
                      "test_f1": results["test"]["f1"],
                      "test_top1": results["test"]["top1"],
                      "val_best_f1": (results["val_best"] or {}).get("f1")}))
    if results["rss_preempted"]:
        sys.exit(3)


if __name__ == "__main__":
    main()
