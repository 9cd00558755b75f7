"""Runner kind `train`: the flagship trainer as a user runs it.

`Code2VecModel` -> `builder.make_train_step` -> `Trainer.train`, fed by
the facade's own batch source from a packed `.c2vb` through
`DevicePrefetcher` and the fused transfer. The harness wraps exactly the
two things it hands to `Trainer`: the batch iterator (to keep the first
batches for the reference, and to end the epoch stream when the window
has closed) and the `train_step` callable (to follow the first steps,
open and close the window, and trace).

One compiled step with one state is built in set-up, driven from the
seed through its first steps (the reference follows three), and that
same object runs the window.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import common, datagen, readers, reference

FOLLOWED_STEPS = 3      # the reference follows these
WARM_STEPS = 12         # steps before the window opens (followed ones too)
TRACE_AFTER_S = 2.0     # a traced run starts its trace this far in
TRACE_FOR_S = 3.0       # and traces this long


def program_argv(cell: common.Cell, prefix: str, seed: int) -> List[str]:
    cfg, traffic = cell.config, cell.traffic
    return (["--data", prefix,
             "--batch_size", str(cfg["batch_rows_per_chip"] * cell.chips),
             "--max_contexts", str(cfg["max_contexts"]),
             "--epochs", "1000000",
             "--seed", str(int(seed) % (2 ** 31 - 1))]
            + list(traffic.get("program_args", [])))


class Program:
    """The system under test: the model, its compiled step, its state."""

    def __init__(self, cell: common.Cell, prefix: str, seed: int,
                 argv: Optional[List[str]] = None,
                 with_train_step: bool = True):
        import jax
        from code2vec_tpu.cli import config_from_args
        from code2vec_tpu.model_facade import Code2VecModel
        self.cell = cell
        common.program_log_to(os.path.join(cell.work, "program.log"))
        self.config = config_from_args(
            argv or program_argv(cell, prefix, seed))
        for key, value in cell.config.get("program_overrides", {}).items():
            setattr(self.config, key, value)
        self.model = Code2VecModel(self.config)
        d = self.model.dims
        self.dims = reference.Dims(d.token_vocab_size, d.path_vocab_size,
                                   d.target_vocab_size, d.token_dim,
                                   d.path_dim)
        want = (cell.config["token_rows"], cell.config["path_rows"],
                cell.config["target_rows"], cell.config["token_dim"],
                cell.config["path_dim"])
        if tuple(self.dims) != want:
            raise common.NoResult(
                f"the program built {tuple(self.dims)}, the configuration "
                f"file says {want}")
        self._shardings = jax.tree.map(lambda x: x.sharding,
                                       self.model.state.params)
        self.train_step = (
            self.model.builder.make_train_step(self.model.state)
            if with_train_step else None)

    def seed_state(self, seed: int):
        """The benchmark's weights from the seed, in place of the
        program's own, float32 as the program holds them; moments and
        step count stay as the program made them. The program's values
        are freed before the seed's arrive, so the device never holds
        two sets and the peak stays the program's own."""
        import jax
        state = self.model.state
        for leaf in jax.tree.leaves(state.params):
            leaf.delete()
        self.model.state = state.replace(params=reference.make_params(
            seed, self.dims, self._shardings))
        return self.model.state

    def fresh_state(self, seed: int):
        """A whole new state from the seed (control readings: many seeds
        in one process, each after the last one's state was freed)."""
        import jax
        from code2vec_tpu.training.state import TrainState
        import jax.numpy as jnp
        opt = self.model.optimizer

        def make(words):
            params = reference.params_from_words(words, self.dims)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt.init(params))
        words = jnp.asarray(reference.seed_words(seed, self.dims))
        if self.model.mesh is None:
            return jax.jit(make)(words)
        from code2vec_tpu.parallel import mesh as mesh_lib
        from code2vec_tpu.training.state import state_spec_tree
        abstract = jax.eval_shape(make, words)
        return jax.jit(make, out_shardings=mesh_lib.shardings(
            self.model.mesh, state_spec_tree(abstract)))(words)

    def first_moments(self, state) -> Dict:
        """Adam's first moment, leaf by leaf, wherever the optimizer
        state keeps it (`mu` of optax's ScaleByAdamState)."""
        import jax
        for node in jax.tree.leaves(
                state.opt_state, is_leaf=lambda n: hasattr(n, "mu")):
            if hasattr(node, "mu"):
                return dict(node.mu)
        raise common.NoResult("no Adam first moment in the optimizer state")


class Follower:
    """Reads, from the program's own state, what the reference is
    compared with: each followed step's loss, the first gradient's norm
    by leaf as the optimizer got it (Adam's first moment after step one
    is (1 - b1) g), and the norm of the parameters' change."""

    def __init__(self, program: Program, seed: int):
        import jax
        import jax.numpy as jnp
        self.program = program
        self.losses: List = []
        self.grad_norms = None
        self.delta_norms = None
        dims = program.dims
        self._words = jnp.asarray(reference.seed_words(seed, dims))
        self._delta = jax.jit(lambda p, words: {
            k: jnp.sqrt(jnp.sum(jnp.square(p[k] - v)))
            for k, v in reference.params_from_words(words, dims).items()})

    def after_step(self, n: int, state, loss) -> None:
        if n > FOLLOWED_STEPS:
            return
        self.losses.append(loss)
        if n == 1:
            mu = self.program.first_moments(state)
            self.grad_norms = reference.leaf_norms(mu)
        if n == FOLLOWED_STEPS:
            self.delta_norms = self._delta(dict(state.params), self._words)

    def read(self) -> reference.Followed:
        b1 = reference.ADAM["b1"]
        return reference.Followed(
            [float(x) for x in self.losses],
            {k: float(v) / (1.0 - b1) for k, v in self.grad_norms.items()},
            {k: float(v) for k, v in self.delta_norms.items()})


class Feed:
    """The batch iterator handed to `Trainer`, wrapped: keeps the host
    arrays of the first batches, marks the host's work for the trace,
    and ends the stream once the window has closed."""

    def __init__(self, batches, keep: int):
        self._it = iter(batches)
        self._keep = keep
        self.kept: List[Dict[str, np.ndarray]] = []
        self.stop = threading.Event()

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        if self.stop.is_set():
            raise StopIteration
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            item = next(self._it)
        if len(self.kept) < self._keep and hasattr(item, "path_indices"):
            self.kept.append({
                "src": np.array(item.source_token_indices, np.int32),
                "pth": np.array(item.path_indices, np.int32),
                "tgt": np.array(item.target_token_indices, np.int32),
                "mask": np.array(item.context_valid_mask, np.float32),
                "labels": np.array(item.target_index, np.int32)})
        return item


class TimedStep:
    """The `train_step` callable handed to `Trainer`, wrapped."""

    def __init__(self, step, follower: Follower, feed: Feed, seconds: float,
                 trace_dir: Optional[str], registry: common.RegistryWindow):
        self.step, self.follower, self.feed = step, follower, feed
        self.seconds, self.trace_dir = seconds, trace_dir
        self.registry = registry
        self.calls = 0
        self.t_open = self.t_close = None
        self.steps_in_window = 0
        self.memory_peak = None
        self.final_state = None
        self._tracing = False
        self._traced = False

    def __call__(self, state, *args):
        import jax
        if self.t_close is not None:     # window closed: drain, no work
            return state, self._last_loss
        self.calls += 1
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, loss = self.step(state, *args)
        self._last_loss = loss
        self.follower.after_step(self.calls, state, loss)
        now = time.perf_counter()
        if self.calls == WARM_STEPS:
            jax.block_until_ready(loss)
            self.registry.open()
            self.t_open = time.perf_counter()
        elif self.t_open is not None:
            self.steps_in_window += 1
            elapsed = now - self.t_open
            if self.trace_dir and not self._traced:
                if not self._tracing and elapsed >= TRACE_AFTER_S:
                    self._start_trace()
                elif self._tracing and elapsed >= TRACE_AFTER_S + TRACE_FOR_S:
                    jax.block_until_ready(loss)
                    jax.profiler.stop_trace()
                    self._tracing, self._traced = False, True
            if elapsed >= self.seconds:
                float(loss)                       # the host fetch
                self.t_close = time.perf_counter()
                self.registry.close()
                if self._tracing:
                    jax.profiler.stop_trace()
                    self._tracing, self._traced = False, True
                self.memory_peak = common.memory_peak_bytes()
                self.final_state = state
                self.feed.stop.set()
        return state, loss

    def _start_trace(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True


def feed_check(kept: List[Dict], packed: str) -> int:
    """How many of the rows fed to the followed steps are not rows of
    the corpus, or came twice. The limit is 0."""
    cache = packed + ".fp.npy"
    if os.path.exists(cache):
        corpus = np.load(cache)
    else:
        corpus = np.sort(datagen.row_fingerprints(datagen.read_corpus(packed)))
        np.save(cache, corpus)
    fed = np.concatenate([datagen.row_fingerprints(np.concatenate(
        [b["labels"][:, None], b["src"], b["pth"], b["tgt"]], axis=1))
        for b in kept])
    pos = np.minimum(np.searchsorted(corpus, fed), len(corpus) - 1)
    foreign = int((corpus[pos] != fed).sum())
    return foreign + int(len(fed) - len(np.unique(fed)))


def compare(program: reference.Followed, ref: reference.Followed,
            limits: Dict, foreign_rows: Optional[int] = None) -> List[Dict]:
    """Each number compared, beside its limit."""
    checks = []

    def add(name, value, limit, note=""):
        ok = bool(np.isfinite(value)) and value <= limit
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": ok, "note": note})
    if foreign_rows is not None:
        add("fed_rows_foreign_or_repeated", foreign_rows, 0)
    for i, (a, b) in enumerate(zip(program.losses, ref.losses), start=1):
        add(f"loss_gap_step{i}", abs(a - b) / abs(b), limits["loss_gap"],
            f"program {a!r} reference {b!r}")
    gap, leaf = reference.worst_leaf_gap(program.grad_norms, ref.grad_norms)
    add("first_grad_norm_gap", gap, limits["first_grad_norm_gap"],
        f"worst leaf {leaf}")
    gap, leaf = reference.worst_leaf_gap(program.delta_norms, ref.delta_norms)
    add("param_change_norm_gap", gap, limits["param_change_norm_gap"],
        f"worst leaf {leaf}")
    return checks


def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    common.configure_jax()
    device = common.require_chips(cell.chips, require_tpu)
    import jax
    from code2vec_tpu import obs
    from code2vec_tpu.training.loop import Trainer
    from code2vec_tpu.training.state import dropout_rng

    data = datagen.prepare_train_data(cell.work, cell.config, cell.traffic)
    common.say(f"data {'made' if data['made'] else 'found'}: {data['rows']} "
               f"rows, mean valid contexts {data['mean_valid_contexts']:.1f}")
    program = Program(cell, data["prefix"], seed)
    state = program.seed_state(seed)
    model, config = program.model, program.config
    follower = Follower(program, seed)
    feed = Feed(model._train_batches(), FOLLOWED_STEPS)
    registry = common.RegistryWindow(obs.default_registry())
    trace_dir = os.path.join(cell.work, "trace") if trace else None
    step = TimedStep(program.train_step, follower, feed, seconds, trace_dir,
                     registry)
    trainer = Trainer(config, step, mesh=model.mesh,
                      steps_per_epoch_hint=model._steps_per_epoch)
    common.say("set-up done up to the first step; training")
    trainer.train(state, feed, dropout_rng(config))
    if step.t_close is None:
        raise common.NoResult("the data ended before the window closed")
    window_s = step.t_close - step.t_open
    setup_s = step.t_open - common.T0
    rows = cell.config["batch_rows_per_chip"] * cell.chips
    examples_per_s = step.steps_in_window * rows / window_s
    common.say(f"window {window_s:.3f}s, {step.steps_in_window} steps")

    got = follower.read()
    foreign = feed_check(feed.kept, data["packed"])
    # the reference runs after the window, once the program's state is
    # freed: its memory and its time are not the program's
    for leaf in jax.tree.leaves(step.final_state):
        leaf.delete()
    t_ref = time.perf_counter()
    ref = reference.follow_steps(
        seed, program.dims, feed.kept, keep=cell.config["dropout_keep"],
        block_rows=cell.traffic.get("reference_block_rows", 256))
    common.say(f"reference followed {FOLLOWED_STEPS} steps in "
               f"{time.perf_counter() - t_ref:.1f}s")
    checks = compare(got, ref, cell.limits(), foreign)
    correct = all(c["ok"] for c in checks)

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": step.memory_peak}
    values = {"examples_per_s": examples_per_s, "setup_s": setup_s}
    result = {"correct": correct, "checks": checks, "values": values,
              "device": dev, "steps": step.steps_in_window,
              "followed": got, "reference": ref}
    if not emit:
        return result
    breakdown = None
    if trace:
        traced = readers.read_traced(
            cell, device["kind"], registry, window_s, trace_dir,
            facts={"rows_per_chip": cell.config["batch_rows_per_chip"],
                   "mean_valid_contexts": data["mean_valid_contexts"]})
        dev.update(traced["device"])
        for name, note in traced["notes"].items():
            common.say(f"{name}: {note}")
        values, breakdown = traced["values"], traced["breakdown"]
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, step.steps_in_window * rows, 0,
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result
