"""Checkpointing via Orbax: trainable vs released artifacts + vocab sidecar.

Reference behavior being reproduced (TPU-natively, not with TF Savers):
- per-epoch checkpoints `<save>_iter<N>` with `max_to_keep` rotation
  (tensorflow_model.py:57, 90-94; config.py:57);
- vocabs stored next to the model as `dictionaries.bin`
  (model_base.py:102-109, config.py:191-194);
- `--release` strips optimizer state for a ~3x smaller inference-only
  artifact (tensorflow_model.py:131-135, keras_model.py:230-234) — here a
  released checkpoint simply omits `opt_state`;
- resume-for-training requires the full artifact (keras_model.py:245-262).

Orbax gives async, sharded, multi-host-safe saves (SURVEY.md §5 plan:
preemption-tolerant checkpointing for TPU pods).

Crash-atomic commit protocol (no reference analog — the reference loses
work on any failure; here the preemption path itself must survive a kill
landing mid-save, since a grace window that expires during `save_model`
would otherwise leave a half-written `_iter<N>` directory that the next
`--load` resume picks by name and dies on):

1. every file is written into a `<base>.tmp-<pid>` staging directory
   (multi-host: ONE shared `<base>.tmp-mh<pid0>` staging dir, named by
   process 0 and broadcast over the coordination KV store — Orbax's
   collective save writes every host's shards into the same tree, which
   per-host staging dirs would tear apart);
2. a manifest (file list + sizes, sha256 of `dictionaries.bin` and the
   meta JSON, an Orbax-completion marker) is recorded LAST, after
   `wait_until_finished`, so its presence certifies the whole artifact;
3. the staging dir is `os.rename`d into place — atomic on POSIX, so a
   crash leaves either the old artifact or the new one, never a blend;
4. orphaned staging dirs from killed saves are swept by checkpoint
   rotation (model_facade._rotate_epoch_checkpoints).

Multi-host pods add a commit-barrier protocol on top (manifest format 2;
ROADMAP's deferred cross-host save-barrier item). All barriers ride the
jax.distributed coordination service (parallel/distributed.py
`commit_barrier`): host-side RPCs with real timeouts, safe on the async
commit thread.

    stage      proc 0 prepares the shared staging dir, broadcasts its
               name; barrier `stage` before any host writes into it
    flush      Orbax collective save + per-host wait_until_finished
    barrier    `commit` — NO host proceeds toward the manifest/rename
               until EVERY host's Orbax flush finished (a host killed
               here fails the barrier on the survivors, the save errors
               out manifest-less, and resume rejects the artifact)
    ack        each host writes `commit_ack.<process_index>` into the
               staged artifact; barrier `acks`
    commit     proc 0 alone writes the manifest (recording
               process_count + the ack set) and performs the atomic
               rename; barrier `committed` releases the peers
    verify     resume rejects any manifest whose recorded ack set is
               not exactly {0..process_count-1}

Async commits (`config.async_checkpointing`) defer everything after the
Orbax dispatch onto an `AsyncCommitter` thread: the step loop's save
stall shrinks to staging + array dispatch, while the barrier + manifest
+ rename + content-hash pass run behind it with bounded in-flight depth
and back-pressure. `drain()` (called in the trainer's `finally` and on
preemption) completes the pipeline deterministically before exit.

Restore is integrity-verified: `verify_checkpoint` re-checks the
manifest, `latest_valid_checkpoint` walks newest -> oldest past any
candidate that fails it, and `load_model` verifies before handing the
directory to Orbax so truncation fails fast with a named file instead of
an opaque pytree error deep in the restore. On a multi-host pod the
fallback walk is COLLECTIVE: hosts agree (min over local bests, re-voted
until unanimous) on one artifact, because each host walking backward
independently can land on different steps and deadlock the pod's
restore-time collectives.

Elastic topology-change restore (manifest format 3): any COMMITTED
artifact is restorable on any host count and mesh shape. The manifest
additionally records the save-time mesh plan (dp/tp/cp), the GLOBAL
parameter-tree structure/shapes/dtypes, and the data-pipeline cursor
(epoch + global row ordinal). `verify_checkpoint` stays strict about
COMMIT completeness (the ack set is checked against the manifest's own
recorded `process_count`, never the restore-time one) — an incomplete
commit is rejected on any topology, while a complete commit made at a
DIFFERENT topology verifies fine and is routed to the resharded-restore
path: `classify_restore` labels it `exact` or `resharded`, and
`load_model` builds its restore targets from the CURRENT mesh's
abstract-array metadata (shape/dtype/sharding of the live state
template) rather than the saved layout, so Orbax reshards params and
optimizer state on read. The collective fallback vote additionally
asserts every host reached the same reshard decision for the agreed
artifact.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import shutil
import threading
from typing import Callable, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from code2vec_tpu import obs
from code2vec_tpu.parallel import distributed
from code2vec_tpu.parallel.distributed import BarrierTimeout  # re-export
from code2vec_tpu.parallel.mesh import MeshPlan
from code2vec_tpu.training.state import TrainState
from code2vec_tpu.utils.faults import fault_point

_STATE_DIR = "state"
_META_NAME = "code2vec_meta.json"
MANIFEST_NAME = "code2vec_manifest.json"
# Format 2 added the multi-host commit-protocol fields: `process_count`
# and `commit_acks` (the participant set that reached the post-flush
# barrier). Format 3 adds the elastic-restore topology record:
# `mesh_plan` (dp/tp/cp at save time), `param_tree` (global shapes and
# dtypes of every state leaf) and `data_cursor` (epoch + global row
# ordinal of the input pipeline). Every addition is strictly additive:
# format-1 (pre-barrier) and format-2 manifests remain loadable, and a
# format-3 manifest read by format-2 code just carries unknown keys.
MANIFEST_FORMAT = 3
ACK_PREFIX = "commit_ack."
RELEASED_SUFFIX = ".release"
# Commit-protocol working dirs: `.tmp-<pid>` is the staging dir a save
# builds in (`.tmp-mh<pid0>` when the pod shares one staging dir);
# `.old-<pid>` briefly holds the previous artifact while a same-path
# overwrite swaps the new one in.
STAGING_INFIX = ".tmp-"
BACKUP_INFIX = ".old-"
_SHARED_STAGING_TAG = "mh"

# Lockstep save ordinal: save_model is a collective call on a pod, so
# every process draws the same ordinal for the same save — it keys the
# barrier/KV names, making each rendezvous unique per save.
_save_ordinal = itertools.count()

# Default cross-host barrier timeout when the config carries none.
DEFAULT_BARRIER_TIMEOUT_S = 600.0

# Small files worth a full content hash in the manifest at save time.
# The Orbax state files are covered by existence+size in the commit-path
# manifest — hashing multi-GB shards before the commit would dominate
# checkpoint time, and Orbax already checksums its own payloads
# internally. Opt-in `config.checkpoint_hash_content` adds full-content
# hashes for everything AFTER the commit (`hash_artifact_content`),
# verified on resume.
_HASHED_FILES = ("dictionaries.bin", _META_NAME)


class CheckpointIntegrityError(RuntimeError):
    """An artifact failed its manifest/structure check. The message names
    the offending file so a truncated/corrupt checkpoint is diagnosable
    without spelunking Orbax internals."""


def _abs(path: str) -> str:
    return os.path.abspath(path)


def is_staging_path(path: str) -> bool:
    """True for commit-protocol working dirs (`<base>.tmp-<pid>` staging,
    `<base>.old-<pid>` overwrite backups) that must never be treated as
    artifacts."""
    name = os.path.basename(path.rstrip(os.sep))
    return STAGING_INFIX in name or BACKUP_INFIX in name


def staging_owner_alive(path: str) -> bool:
    """Does the process that created this staging/backup dir still run?
    Used by the sweeper so a concurrent save's in-flight staging dir is
    left alone while leftovers of killed saves are reclaimed. Unparseable
    names are treated as orphaned. Shared multi-host staging dirs
    (`.tmp-mh<pid0>`) are owned by process 0 — which is also the only
    process that runs the sweeper on a pod, so the liveness probe always
    runs on the machine that owns the pid."""
    name = os.path.basename(path.rstrip(os.sep))
    for infix in (STAGING_INFIX, BACKUP_INFIX):
        if infix in name:
            tail = name.rsplit(infix, 1)[1]
            break
    else:
        return False
    if tail.startswith(_SHARED_STAGING_TAG):
        tail = tail[len(_SHARED_STAGING_TAG):]
    try:
        pid = int(tail)
    except ValueError:
        return False
    if pid == os.getpid():
        return True
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by another user


def parse_iter_name(path: str):
    """Parse a `<base>_iter<N>[_preempt]` artifact path into
    (epoch, is_preempt), or None if the tail is not of that form. Single
    source of truth for the epoch-checkpoint naming convention (written
    by model_facade's save_fn; consumed by rotation and resume). Staging
    dirs (`..._iter<N>.tmp-<pid>`) parse as None, so every consumer
    ignores them for free."""
    if "_iter" not in path:
        return None
    tail = path.rsplit("_iter", 1)[1]
    preempt = tail.endswith("_preempt")
    if preempt:
        tail = tail[: -len("_preempt")]
    try:
        return int(tail), preempt
    except ValueError:
        return None


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def hash_artifact_content(base: str, max_threads: int = 4) -> dict:
    """Record a full-content sha256 for EVERY manifest-listed file —
    including the multi-GB Orbax shards the manifest otherwise only
    size-checks — and rewrite the manifest atomically (tmp + rename).

    Meant to run AFTER the atomic commit (`config.checkpoint_hash_content`
    in save_model), so the hashing of large shards never extends the
    window in which a kill loses the save: a crash mid-hash just leaves a
    valid artifact without content hashes. Incremental 1 MB chunks on a
    thread pool (hashlib releases the GIL, so hashing overlaps I/O and
    scales past one core). Returns the updated manifest."""
    from concurrent.futures import ThreadPoolExecutor

    with obs.span("checkpoint_content_hash",
                  hist=obs.histogram(
                      "checkpoint_content_hash_seconds",
                      "post-commit full-content sha256 of one artifact")):
        manifest_path = os.path.join(base, MANIFEST_NAME)
        with open(manifest_path) as f:
            manifest = json.load(f)
        rels = sorted(manifest["files"])
        with ThreadPoolExecutor(max_workers=max_threads) as pool:
            digests = pool.map(
                lambda rel: _sha256_file(os.path.join(base, rel)), rels)
        for rel, digest in zip(rels, digests):
            manifest["files"][rel]["content_sha256"] = digest
        manifest["content_hashed"] = True
        tmp = manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=2)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, manifest_path)
        return manifest


def _fsync_dir(path: str) -> None:
    """Durably record a directory entry (the rename commit). Best-effort:
    some filesystems refuse O_RDONLY fsync on directories."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def write_commit_ack(staging: str, index: int) -> str:
    """Record this host's commit acknowledgment inside the staged
    artifact: a tiny `commit_ack.<process_index>` file proving the host
    survived to the post-flush barrier. The manifest (written after the
    ack barrier) records the full ack set; resume rejects artifacts
    whose recorded participant set is incomplete."""
    path = os.path.join(staging, f"{ACK_PREFIX}{index}")
    with open(path, "w") as f:
        json.dump({"process_index": index, "pid": os.getpid()}, f)
        f.flush()
        os.fsync(f.fileno())
    obs.counter("checkpoint_commit_acks_total",
                "per-host commit acknowledgments written after the "
                "post-flush barrier").inc()
    return path


def tree_summary(tree) -> dict:
    """Flatten a state pytree into {leaf path: {shape, dtype}} with
    GLOBAL shapes (a sharded jax.Array's `.shape` is its global shape).
    Recorded into the format-3 manifest so a restore onto any topology
    can check structural compatibility up front — a mismatched
    embedding size or optimizer layout fails with the offending leaf
    named instead of an opaque Orbax pytree error mid-restore."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        out[jax.tree_util.keystr(path)] = {
            "shape": [int(d) for d in getattr(leaf, "shape", ())],
            "dtype": str(getattr(leaf, "dtype", type(leaf).__name__)),
        }
    return out


def load_manifest(model_path: str) -> Optional[dict]:
    """The artifact's manifest dict, or None for pre-manifest (legacy)
    artifacts / unreadable files. Read-only convenience for the elastic
    restore path (topology classification + data cursor); integrity
    checking stays `verify_checkpoint`'s job."""
    path = os.path.join(_abs(model_path), MANIFEST_NAME)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return None
    return manifest if isinstance(manifest, dict) else None


def _config_mesh_plan(config) -> MeshPlan:
    """The run's mesh plan, tolerating config-like objects without the
    mesh knobs (missing axes default to 1, like an unset config)."""
    return MeshPlan(dp=int(getattr(config, "dp", 1)),
                    tp=int(getattr(config, "tp", 1)),
                    cp=int(getattr(config, "cp", 1)))


def classify_restore(manifest: Optional[dict], config=None) -> str:
    """Label a restore of a COMMITTED artifact under the current
    topology: "exact" (same process count and — when `config` is given —
    same dp/tp/cp mesh plan as at save time) or "resharded" (any
    difference; Orbax rebuilds the arrays against the current mesh's
    shardings). Legacy manifests without topology fields classify as
    "exact": they carry no record to differ from.

    Completeness is NOT judged here — `verify_checkpoint` rejects
    incomplete commits against the manifest's own recorded process
    count; this function only routes complete ones."""
    if not manifest:
        return "exact"
    saved_procs = manifest.get("process_count")
    if (saved_procs is not None
            and int(saved_procs) != distributed.process_count()):
        return "resharded"
    plan = manifest.get("mesh_plan")
    if (isinstance(plan, dict) and config is not None
            and MeshPlan.from_dict(plan) != _config_mesh_plan(config)):
        return "resharded"
    return "exact"


def _check_param_tree(manifest: Optional[dict], template, base: str) -> None:
    """Compare the manifest's recorded global parameter tree against the
    restore template; raise ValueError naming the first offending leaf.
    Only leaves the template wants are checked (a released load ignores
    the artifact's opt_state record and vice versa); manifests without
    the record (formats 1/2) skip the check."""
    saved = manifest.get("param_tree") if manifest else None
    if not isinstance(saved, dict):
        return
    want = tree_summary(template)
    missing = sorted(set(want) - set(saved))
    if missing:
        raise ValueError(
            f"{base}: restore template expects leaf {missing[0]} but the "
            f"artifact's recorded parameter tree has no such leaf — the "
            f"saved model/optimizer structure differs from this run's "
            f"configuration ({len(missing)} leaves missing in total).")
    for key, entry in sorted(want.items()):
        rec = saved[key]
        if list(rec.get("shape", ())) != entry["shape"]:
            raise ValueError(
                f"{base}: leaf {key} was saved with global shape "
                f"{rec.get('shape')} but this run expects "
                f"{entry['shape']}; the model configuration (vocab or "
                f"embedding sizes) differs from the artifact's. Note "
                f"that table rows are padded to a multiple of tp — a "
                f"mesh reshape needs a tp under which the padded shapes "
                f"agree with the artifact's.")
        if rec.get("dtype") != entry["dtype"]:
            raise ValueError(
                f"{base}: leaf {key} was saved as {rec.get('dtype')} but "
                f"this run expects {entry['dtype']}; match the precision "
                f"flags the artifact was saved with.")


def _write_manifest(base: str, epoch: int, released: bool,
                    process_count: int = 1,
                    topology: Optional[dict] = None) -> None:
    """Record every file in the (staged) artifact with its size, plus
    content hashes for the small sidecars. Written last: its presence is
    the Orbax-completion marker — `save_model` only writes it after
    `wait_until_finished` (and, on a pod, after the cross-host commit
    barrier), so a manifest-bearing directory is a fully flushed
    artifact. Records the participating process count and the commit-ack
    set found on disk; a manifest whose ack set is short of its
    process_count is rejected at verify time."""
    files = {}
    acks = []
    for root, _dirs, names in os.walk(base):
        for name in names:
            p = os.path.join(root, name)
            rel = os.path.relpath(p, base)
            if rel == MANIFEST_NAME:
                continue
            if rel.startswith(ACK_PREFIX) and os.sep not in rel:
                try:
                    acks.append(int(rel[len(ACK_PREFIX):]))
                except ValueError:
                    pass
            entry = {"size": os.path.getsize(p)}
            if rel in _HASHED_FILES:
                entry["sha256"] = _sha256_file(p)
            files[rel] = entry
    if process_count == 1 and not acks:
        acks = [0]  # single-process saves carry no ack files
    manifest = {
        "format": MANIFEST_FORMAT,
        "epoch": epoch,
        "released": released,
        "orbax_complete": True,
        "process_count": process_count,
        "commit_acks": sorted(acks),
        "files": files,
    }
    if topology:
        manifest.update(topology)
    path = os.path.join(base, MANIFEST_NAME)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=2)
        f.flush()
        os.fsync(f.fileno())


def _commit_staging(staging: str, base: str) -> None:
    """Atomically promote a fully written staging dir to the final path.
    Overwrites swap through a `.old-<pid>` backup so there is never a
    moment with no artifact at `base`; a kill mid-swap leaves the backup
    for the sweeper and the verifier-guided fallback to sort out."""
    fault_point("checkpoint_commit")
    if os.path.isdir(base):
        backup = f"{base}{BACKUP_INFIX}{os.getpid()}"
        if os.path.isdir(backup):
            shutil.rmtree(backup)
        os.rename(base, backup)
        # A kill in this window leaves NOTHING at `base` but two intact
        # copies (`.tmp-` new, `.old-` previous); the sweeper promotes
        # whichever verifies (reclaim_orphan) instead of deleting them.
        fault_point("checkpoint_swap")
        os.rename(staging, base)
        shutil.rmtree(backup, ignore_errors=True)
    else:
        os.rename(staging, base)
    _fsync_dir(os.path.dirname(base) or ".")


def reclaim_orphan(path: str,
                   log: Optional[Callable[[str], None]] = None) -> str:
    """Reclaim one orphaned commit-protocol dir (a `.tmp-`/`.old-` whose
    owning process is gone). If the final name is unoccupied and the
    orphan passes verification — the kill-between-swap-renames window
    leaves exactly that — it is PROMOTED back via rename (a complete
    artifact must never be deleted while its slot sits empty); anything
    else is removed. Returns "promoted" or "removed"."""
    dirpart, name = os.path.split(os.path.abspath(path.rstrip(os.sep)))
    for infix in (STAGING_INFIX, BACKUP_INFIX):
        if infix in name:
            base = os.path.join(dirpart, name.rsplit(infix, 1)[0])
            break
    else:
        return "removed"  # not a commit-protocol dir; caller filtered wrong
    if not os.path.exists(base):
        try:
            verify_checkpoint(path)
        except CheckpointIntegrityError:
            pass
        else:
            os.rename(path, base)
            _fsync_dir(dirpart)
            if log is not None:
                log(f"Promoted orphaned-but-complete checkpoint {path} "
                    f"back to {base} (save was killed mid-commit)")
            return "promoted"
    shutil.rmtree(path, ignore_errors=True)
    return "removed"


def verify_checkpoint(model_path: str, check_content: bool = False) -> dict:
    """Probe an artifact against its manifest; returns the parsed meta on
    success, raises CheckpointIntegrityError naming the first offending
    file otherwise. Cheap by design (stat per file, hash only the small
    sidecars), so resume can probe a fallback chain and rotation can
    re-check candidates without meaningful cost.

    `check_content=True` additionally re-hashes every file carrying a
    post-commit `content_sha256` (written when the save ran with
    `checkpoint_hash_content`) — the resume path's deep probe; the
    rotation/fallback walks keep the cheap default.

    Pre-manifest (legacy) artifacts get a structural probe instead:
    required files present, meta parseable, Orbax state dir non-empty —
    enough to reject the blatant half-writes the old layout could leave.
    """
    with obs.span("checkpoint_verify",
                  hist=obs.histogram("checkpoint_verify_seconds",
                                     "manifest probe of one artifact")):
        try:
            return _verify_checkpoint_inner(model_path, check_content)
        except CheckpointIntegrityError:
            obs.counter("checkpoint_verify_failures_total",
                        "artifacts that failed their integrity check "
                        "(resume fallback walked past them)").inc()
            raise


def _verify_checkpoint_inner(model_path: str,
                             check_content: bool = False) -> dict:
    base = _abs(model_path)
    if not os.path.isdir(base):
        raise CheckpointIntegrityError(f"{base}: not a directory")
    manifest_path = os.path.join(base, MANIFEST_NAME)
    if not os.path.isfile(manifest_path):
        return _verify_legacy(base)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointIntegrityError(
            f"{manifest_path}: unreadable or corrupt manifest ({e})")
    if not isinstance(manifest, dict) or not isinstance(
            manifest.get("files"), dict):
        raise CheckpointIntegrityError(
            f"{manifest_path}: malformed manifest (no file table)")
    if not manifest.get("orbax_complete"):
        raise CheckpointIntegrityError(
            f"{manifest_path}: Orbax completion marker missing — the save "
            f"was interrupted before wait_until_finished")
    if "process_count" in manifest:
        # Manifest format 2+: the save recorded its participant set. An
        # incomplete ack set means a host died between the commit
        # barrier and the manifest (or the manifest was hand-edited);
        # its shards may be missing from the artifact, so reject it.
        # The check is against the manifest's OWN process_count — never
        # the restore-time one — so a COMPLETE commit made at a
        # different topology verifies fine (classify_restore routes it
        # to the resharded-restore path); only INCOMPLETE commits are
        # rejected.
        want = int(manifest["process_count"])
        acks = manifest.get("commit_acks")
        try:
            got = (sorted({int(a) for a in acks})
                   if isinstance(acks, list) else None)
        except (TypeError, ValueError):
            got = None
        if got != list(range(want)):
            raise CheckpointIntegrityError(
                f"{manifest_path}: commit-ack participant set {got} is "
                f"not the full {want}-process set — a host did not "
                f"survive to the commit barrier; its shards cannot be "
                f"trusted to be in this artifact")
    for rel, entry in manifest["files"].items():
        p = os.path.join(base, rel)
        if not os.path.isfile(p):
            raise CheckpointIntegrityError(f"{p}: listed in manifest but missing")
        try:
            size = os.path.getsize(p)
            if size != entry.get("size"):
                raise CheckpointIntegrityError(
                    f"{p}: size {size} != manifest size {entry.get('size')} "
                    f"(truncated or partially written)")
            want_hash = entry.get("sha256")
            content_hash = (entry.get("content_sha256") if check_content
                            else None)
            if want_hash or content_hash:
                digest = _sha256_file(p)  # one pass serves both checks
                if want_hash and digest != want_hash:
                    raise CheckpointIntegrityError(
                        f"{p}: sha256 mismatch against manifest (corrupt)")
                if content_hash and digest != content_hash:
                    raise CheckpointIntegrityError(
                        f"{p}: content sha256 mismatch against manifest "
                        f"(bit-rot or size-preserving corruption)")
        except OSError as e:
            # A file that vanishes BETWEEN the isfile() probe and the
            # stat/hash is an artifact being swapped underneath us — on a
            # multi-host pod every host runs the same commit (staging
            # rename + backup swap) on the same final path, so a peer's
            # commit window can briefly empty the directory a rotation
            # probe is walking (the cross-host save barrier is a known
            # ROADMAP item). Degrade to the integrity error the callers
            # are built to tolerate (fallback walks skip the candidate;
            # resume retries older) instead of crashing the trainer.
            raise CheckpointIntegrityError(
                f"{p}: vanished or became unreadable mid-probe ({e}) — "
                f"concurrent commit/rotation by another process")
    return _load_meta_checked(base)


def _verify_legacy(base: str) -> dict:
    for rel in ("dictionaries.bin", _META_NAME):
        if not os.path.isfile(os.path.join(base, rel)):
            raise CheckpointIntegrityError(
                f"{os.path.join(base, rel)}: required file missing "
                f"(no manifest to consult; pre-manifest artifact)")
    meta = _load_meta_checked(base)
    state_dir = os.path.join(base, _STATE_DIR)
    if not os.path.isdir(state_dir) or not os.listdir(state_dir):
        raise CheckpointIntegrityError(
            f"{state_dir}: Orbax state directory missing or empty")
    return meta


def _load_meta_checked(base: str) -> dict:
    meta_path = os.path.join(base, _META_NAME)
    try:
        with open(meta_path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointIntegrityError(
            f"{meta_path}: unreadable or corrupt meta ({e})")


def _candidate_key(parsed) -> int:
    """Encode (epoch, is_preempt) as one integer preserving the resume
    preference order (newer epoch wins; at equal epoch the preemption
    artifact wins — see latest_valid_checkpoint)."""
    epoch, preempt = parsed
    return epoch * 2 + (1 if preempt else 0)


def _candidate_path(save_base: str, key: int) -> str:
    epoch, preempt = key // 2, bool(key % 2)
    return f"{save_base}_iter{epoch}" + ("_preempt" if preempt else "")


def _local_latest_valid(save_base: str, excluded,
                        log: Optional[Callable[[str], None]] = None,
                        trail: Optional[list] = None):
    """This host's newest verifying candidate (key, path), skipping any
    key in `excluded`; (None, None) if nothing verifies. `trail`, when
    given, collects one record per candidate CONSIDERED — the resume
    path surfaces it so a run that fell back past rejected artifacts
    says so loudly instead of silently starting older (or fresh)."""
    import glob
    candidates = []  # ((epoch, is_preempt), path)
    for p in glob.glob(save_base + "_iter*"):
        parsed = parse_iter_name(p)
        if parsed is None or _candidate_key(parsed) in excluded:
            continue
        candidates.append((parsed, p))
    for parsed, path in sorted(candidates, reverse=True):
        try:
            verify_checkpoint(path)
            if trail is not None:
                trail.append({"path": path, "outcome": "selected",
                              "reason": "passes verification"})
            return _candidate_key(parsed), path
        except CheckpointIntegrityError as e:
            obs.counter(
                "resume_artifacts_rejected_total",
                "resume candidates the fallback walk rejected").inc()
            if trail is not None:
                trail.append({"path": path, "outcome": "rejected",
                              "reason": str(e)})
            if log is not None:
                log(f"Skipping corrupt/partial checkpoint {path}: {e}")
    return None, None


def latest_valid_checkpoint(save_base: str,
                            log: Optional[Callable[[str], None]] = None,
                            collective: Optional[bool] = None,
                            trail: Optional[list] = None):
    """Newest `<save_base>_iter<N>[_preempt]` artifact that PASSES its
    integrity check (None if no candidate does). Walks newest -> oldest
    past corrupt/partial artifacts, logging each skip, so a save killed
    mid-write (or a disk that ate a file) costs at most the epochs since
    the last valid artifact instead of the whole run.

    At equal N the preemption artifact wins: it was written mid-epoch
    N+1, so its params are strictly more trained than the clean
    end-of-epoch-N save.

    On a multi-host pod (`collective=None` auto-detects; pass False to
    force a host-local walk, e.g. post-mortem tooling) the walk is a
    COLLECTIVE agreement: each host proposes its local best, the pod
    takes the minimum (the newest artifact every host accepts can only
    be <= each local best), every host re-verifies that candidate, and
    the vote repeats with the candidate excluded until unanimous — all
    hosts return the SAME path (or all None). Without this, hosts whose
    independent backward walks diverge restore different steps and
    deadlock the pod's first collective. The agreement covers the
    RESHARD decision too: once a path is unanimous, every host
    classifies it against the current topology and a divergence (e.g.
    one host reading a stale manifest copy) raises the loud desync
    error instead of letting the pod split between an exact and a
    resharded restore. Runs host collectives: main thread only."""
    if collective is None:
        collective = distributed.process_count() > 1
    if not collective or distributed.process_count() == 1:
        return _local_latest_valid(save_base, excluded=(), log=log,
                                   trail=trail)[1]
    excluded = set()
    while True:
        local_key, _local_path = _local_latest_valid(save_base, excluded,
                                                     log, trail=trail)
        proposal = -1 if local_key is None else local_key
        agreed = distributed.agree_scalar(proposal, "min")
        if agreed < 0:
            # At least one host verifies NOTHING (it also vetoes every
            # newer candidate its peers hold): resuming a subset would
            # desync the pod, so all hosts consistently start fresh.
            return None
        path = _candidate_path(save_base, agreed)
        try:
            verify_checkpoint(path)
            ok = 1.0
        except CheckpointIntegrityError as e:
            ok = 0.0
            if log is not None:
                log(f"Pod-agreed candidate {path} fails verification on "
                    f"this host: {e}")
        votes = distributed.allreduce_host_scalars(np.array([ok]))[0]
        if int(votes) == distributed.process_count():
            if log is not None and excluded:
                log(f"Pod agreed on fallback checkpoint {path} after "
                    f"excluding {len(excluded)} candidate(s)")
            # The reshard decision is part of the agreement: every host
            # must read the same manifest the same way, or the pod's
            # restore would mix exact and resharded templates.
            decision = (0 if classify_restore(load_manifest(path)) == "exact"
                        else 1)
            distributed.assert_host_agreement(
                decision, f"reshard decision for {os.path.basename(path)}")
            return path
        excluded.add(agreed)


# Back-compat name: the pre-manifest API returned the newest artifact by
# name alone; every caller now gets the verified walk.
latest_checkpoint = latest_valid_checkpoint


def resolve_load_path(model_load_path: str,
                      log: Optional[Callable[[str], None]] = None,
                      trail: Optional[list] = None) -> str:
    """Resolve a `--load` argument: a concrete artifact directory is
    returned as-is; anything else is treated as a save base and resolved
    to its newest VALID `_iter<N>` artifact, so resuming after a crash
    never requires the operator to guess which directory survived.
    `trail` collects the candidates considered/rejected along the way so
    the caller can report a degraded resume loudly."""
    base = _abs(model_load_path)
    if os.path.isdir(base) and (
            os.path.isfile(os.path.join(base, _META_NAME))
            or os.path.isfile(os.path.join(base, MANIFEST_NAME))):
        return base
    found = latest_valid_checkpoint(base, log=log, trail=trail)
    return found if found is not None else base


class AsyncCommitter:
    """Bounded background pipeline for the deferred half of a save.

    `save_model(..., committer=...)` stages the artifact and dispatches
    the Orbax write synchronously, then hands the rest — Orbax
    wait_until_finished, the cross-host commit barrier, acks, manifest,
    atomic rename, content-hash pass — to this single commit thread.
    The step loop's save stall shrinks to staging + dispatch.

    Guarantees kept from the synchronous protocol:
    - bounded in-flight depth with BACK-PRESSURE: `submit` blocks once
      `max_in_flight` commits are pending, so a slow filesystem can
      never queue unbounded half-finished saves;
    - commit failures are never silent: the first error re-raises on
      the next `submit` or `drain` (the trainer drains in its
      `finally`, so a failed commit fails the run);
    - `drain()` completes every pending commit deterministically —
      the preemption path drains BEFORE writing its own artifact, so
      exit always leaves a fully committed, resumable state."""

    def __init__(self, max_in_flight: int = 2,
                 log: Optional[Callable[[str], None]] = None):
        from concurrent.futures import ThreadPoolExecutor
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="c2v-ckpt-commit")
        self._slots = threading.Semaphore(max(1, int(max_in_flight)))
        self._lock = threading.Lock()
        self._futures = []
        self._errors = []
        self._depth = 0
        self._log = log
        self._g_depth = obs.gauge(
            "checkpoint_async_inflight",
            "async checkpoint commits currently pending")

    @property
    def in_flight(self) -> int:
        with self._lock:
            return self._depth

    def raise_pending(self) -> None:
        """Re-raise the first recorded commit failure (original
        exception object, so fault-injection drills see their own
        types). Clears it: the caller owns the error once raised."""
        with self._lock:
            if not self._errors:
                return
            label, err = self._errors.pop(0)
        raise err

    def submit(self, job: Callable[[], object], label: str) -> None:
        self.raise_pending()
        with obs.span("checkpoint_async_backpressure",
                      hist=obs.histogram(
                          "checkpoint_async_backpressure_seconds",
                          "save stalled waiting for an in-flight async "
                          "commit slot")):
            self._slots.acquire()  # back-pressure at max_in_flight

        def run():
            try:
                with obs.span("checkpoint_async_commit",
                              hist=obs.histogram(
                                  "checkpoint_async_commit_seconds",
                                  "deferred commit: orbax wait + barrier "
                                  "+ manifest + rename")):
                    job()
            except BaseException as e:  # noqa: BLE001 — surfaced on drain
                with self._lock:
                    self._errors.append((label, e))
                obs.counter("checkpoint_async_errors_total",
                            "async checkpoint commits that failed").inc()
                if self._log is not None:
                    self._log(f"Async checkpoint commit {label} FAILED: "
                              f"{type(e).__name__}: {e}")
            finally:
                with self._lock:
                    self._depth -= 1
                    self._g_depth.set(self._depth)
                self._slots.release()

        with self._lock:
            self._futures = [f for f in self._futures if not f.done()]
            self._futures.append(self._executor.submit(run))
            self._depth += 1
            self._g_depth.set(self._depth)

    def drain(self) -> None:
        """Block until every pending commit finished; re-raise the first
        failure. Idempotent and safe to call with nothing in flight."""
        from concurrent.futures import wait
        with self._lock:
            pending = list(self._futures)
        if pending:
            wait(pending)
        self.raise_pending()

    def close(self) -> None:
        """Drain (surfacing errors) and stop the commit thread."""
        try:
            self.drain()
        finally:
            self._executor.shutdown(wait=True)


def save_model(model_save_path: str, state: TrainState, vocabs, config,
               epoch: int = 0, released: bool = False,
               committer: Optional[AsyncCommitter] = None,
               on_committed: Optional[Callable[[], None]] = None,
               data_cursor: Optional[dict] = None) -> str:
    """Save a standalone model artifact at `<model_save_path>` (a directory
    is created): Orbax state + `dictionaries.bin` + config meta. Mirrors
    `Code2VecModelBase.save` (model_base.py:102-109).

    Crash-atomic: everything lands in a staging dir, the manifest is
    recorded last, and the staging dir is renamed into place (see the
    commit protocol in the module docstring). Multi-host pods add the
    commit-barrier protocol; the save is a COLLECTIVE call there. The
    `save` fault points between the steps are inert in production and
    let tests/test_chaos.py kill the save at every interesting boundary.

    With `committer` (async mode) the call returns after staging +
    Orbax dispatch; flush/barrier/manifest/rename run on the commit
    thread and `on_committed` (e.g. checkpoint rotation) fires there
    after a successful commit. The returned path is where the artifact
    WILL commit; callers needing it durable must drain the committer.

    `data_cursor` ({"epoch", "global_row_ordinal", ...}) is recorded
    verbatim into the format-3 manifest — the input-pipeline position
    this state corresponds to, which an elastic resume remaps to the new
    host count so no row is skipped or double-read."""
    with obs.span("checkpoint_save",
                  hist=obs.histogram(
                      "checkpoint_save_seconds",
                      "step-loop save stall: stage + flush + commit "
                      "(sync) or stage + dispatch (async)")):
        return _save_model_inner(model_save_path, state, vocabs, config,
                                 epoch, released, committer, on_committed,
                                 data_cursor)


def _barrier_timeout_s(config) -> float:
    return float(getattr(config, "save_barrier_timeout_s", 0)
                 or DEFAULT_BARRIER_TIMEOUT_S)


def _save_model_inner(model_save_path: str, state: TrainState, vocabs,
                      config, epoch: int, released: bool,
                      committer: Optional[AsyncCommitter] = None,
                      on_committed: Optional[Callable[[], None]] = None,
                      data_cursor: Optional[dict] = None) -> str:
    base = _abs(model_save_path) + (RELEASED_SUFFIX if released else "")
    nprocs = distributed.process_count()
    multi = nprocs > 1
    ordinal = next(_save_ordinal)  # lockstep: save_model is collective
    timeout_s = _barrier_timeout_s(config)
    if multi:
        # ONE shared staging dir for the whole pod (Orbax's collective
        # save interleaves every host's shards into the same tree), its
        # name chosen by process 0 and spread over the coordination KV
        # store. Process 0 prepares it; the `stage` barrier keeps peers
        # from writing into a directory that does not exist yet.
        proposal = (f"{base}{STAGING_INFIX}{_SHARED_STAGING_TAG}"
                    f"{os.getpid()}" if distributed.process_index() == 0
                    else None)
        staging = distributed.broadcast_from_primary(
            f"c2v:staging:{ordinal}:{os.path.basename(base)}", proposal,
            timeout_s)
        if distributed.process_index() == 0:
            if os.path.isdir(staging):
                shutil.rmtree(staging)  # leftover from a failed save
            os.makedirs(staging)
        distributed.commit_barrier(f"c2v:stage:{ordinal}", timeout_s)
    else:
        staging = f"{base}{STAGING_INFIX}{os.getpid()}"
        if os.path.isdir(staging):
            shutil.rmtree(staging)  # leftover from a failed save by this pid
        os.makedirs(staging)
    committing_host = not multi or distributed.process_index() == 0
    fault_point("save")   # 1: staging created, nothing written
    if committing_host:
        vocabs.save(os.path.join(staging, "dictionaries.bin"))
    fault_point("save")   # 2: vocab written, meta missing
    if committing_host:
        with open(os.path.join(staging, _META_NAME), "w") as f:
            json.dump({
                "released": released,
                "epoch": epoch,
                "step": int(np.asarray(state.step)),
                "token_vocab_size": vocabs.token_vocab.size,
                "path_vocab_size": vocabs.path_vocab.size,
                "target_vocab_size": vocabs.target_vocab.size,
                "token_embeddings_size": config.token_embeddings_size,
                "path_embeddings_size": config.path_embeddings_size,
                "separate_oov_and_pad": config.separate_oov_and_pad,
                # opt_state pytree structure depends on the update mode;
                # recorded so a mode mismatch fails with a clear error at
                # restore time instead of an opaque Orbax structure
                # mismatch.
                "use_sparse_embedding_update": bool(
                    getattr(config, "use_sparse_embedding_update", False)),
                # Adam moment dtypes shape the opt_state arrays; a restore
                # into a template with different dtypes can error or
                # silently cast depending on the Orbax version, so they're
                # recorded and checked like the sparse-mode flag above.
                "adam_mu_dtype": str(
                    getattr(config, "adam_mu_dtype", "float32")),
                "adam_nu_dtype": str(
                    getattr(config, "adam_nu_dtype", "float32")),
            }, f, indent=2)
    fault_point("save")   # 3: meta written, Orbax state missing
    # Orbax dispatch is synchronous in BOTH modes (it snapshots the
    # arrays); the flush wait is what async mode defers.
    ckptr = ocp.StandardCheckpointer()
    target = {"params": state.params, "step": state.step}
    if not released:
        target["opt_state"] = state.opt_state
    state_dir = os.path.join(staging, _STATE_DIR)
    ckptr.save(state_dir, target, force=True)

    # Format-3 topology record, captured host-side before the deferred
    # commit: the save-time mesh plan, the GLOBAL tree structure (a
    # sharded jax.Array's .shape is global), and the data cursor — what
    # an elastic restore needs to reshard onto any topology and resume
    # the input pipeline without skipping or double-reading rows.
    topology = {
        "mesh_plan": _config_mesh_plan(config).to_dict(),
        "param_tree": tree_summary(target),
    }
    if data_cursor is not None:
        topology["data_cursor"] = dict(data_cursor)

    def commit_job():
        try:
            with obs.span("checkpoint_orbax_flush",
                          hist=obs.histogram(
                              "checkpoint_orbax_flush_seconds",
                              "Orbax wait_until_finished (the bulk "
                              "bytes reaching disk)")):
                ckptr.wait_until_finished()
        finally:
            ckptr.close()
        fault_point("save")   # 4: Orbax flushed, manifest missing
        fault_point("async_commit")  # deferred commit work begins
        if multi:
            fault_point("barrier_enter")
            with obs.span("checkpoint_commit_barrier",
                          hist=obs.histogram(
                              "checkpoint_barrier_wait_seconds",
                              "wait at the cross-host post-flush commit "
                              "barrier")):
                distributed.commit_barrier(f"c2v:commit:{ordinal}",
                                           timeout_s)
            # every host survived the flush: ack, then wait for all acks
            write_commit_ack(staging, distributed.process_index())
            distributed.commit_barrier(f"c2v:acks:{ordinal}", timeout_s)
        if committing_host:
            _write_manifest(staging, epoch, released, process_count=nprocs,
                            topology=topology)
            fault_point("save")   # 5: fully staged, not yet committed
            _commit_staging(staging, base)
        fault_point("callback_crash")  # committed, completion pending
        if multi:
            # peers return only once the artifact is liftable at `base`
            distributed.commit_barrier(f"c2v:committed:{ordinal}",
                                       timeout_s)
        if committing_host and getattr(config, "checkpoint_hash_content",
                                       False):
            # Post-commit by design: the artifact is already durable, so
            # hashing the multi-GB shards never widens the crash window —
            # a kill mid-hash just leaves a valid artifact without
            # content hashes (which resume then simply doesn't check).
            try:
                hash_artifact_content(base)
            except OSError:
                # a peer's commit swapped the artifact mid-hash (the same
                # race verify_checkpoint degrades gracefully); the
                # surviving copy is covered by its own writer's hash pass
                obs.counter(
                    "checkpoint_content_hash_races_total",
                    "post-commit hash passes abandoned because a peer "
                    "swapped the artifact underneath them").inc()
        obs.counter("checkpoint_saves_total",
                    "committed checkpoint artifacts").inc()
        obs.gauge("checkpoint_last_save_unixtime",
                  "wall clock of the last committed save"
                  ).set_to_current_time()
        obs.gauge("checkpoint_last_save_epoch",
                  "epoch recorded in the last committed save").set(epoch)
        if on_committed is not None:
            on_committed()
        return base

    if committer is None:
        commit_job()
    else:
        try:
            committer.submit(commit_job,
                             label=f"{os.path.basename(base)}@{ordinal}")
        except BaseException:
            # submit resurfaced an EARLIER commit's failure before
            # accepting this job — but this save's Orbax write is
            # already dispatched and still streaming into the staging
            # dir. Settle it before re-raising, or a retry's staging
            # cleanup races the orphaned background write.
            try:
                ckptr.wait_until_finished()
            except Exception:
                pass
            finally:
                ckptr.close()
            raise
    return base


def load_model_meta(model_load_path: str) -> dict:
    base = _abs(model_load_path)
    with open(os.path.join(base, _META_NAME)) as f:
        return json.load(f)


def _abstract_restore_template(tree):
    """Restore targets built from the CURRENT state's abstract-array
    metadata: every live jax.Array leaf becomes a ShapeDtypeStruct
    carrying its (current-mesh) sharding, so Orbax lays the restored
    arrays out for the topology the run HAS, not the one the artifact
    was saved under — the mechanism behind elastic N->M restore. Host
    (numpy) leaves stay concrete and restore host-side as before."""
    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)
        return x
    return jax.tree.map(leaf, tree)


def load_model(model_load_path: str, state_like: TrainState,
               config=None, params_only: bool = False,
               report: Optional[dict] = None) -> TrainState:
    """Restore a standalone artifact saved by `save_model`. `state_like`
    provides structure/shardings; released artifacts keep `state_like`'s
    (fresh) optimizer state. `params_only` restores just params+step and
    never touches the saved optimizer state — the `--release` path, which
    must load artifacts regardless of their optimizer layout/dtypes (it
    is the advertised escape hatch for every optimizer-mismatch error
    below, so it cannot itself run those checks).

    The artifact is manifest-verified FIRST, so a truncated or
    half-written directory fails fast with the offending file named
    instead of surfacing as an opaque Orbax pytree error mid-restore.
    Resume is the deep probe: post-commit content hashes (saves made
    with `checkpoint_hash_content`) are re-checked here when present.

    Topology is ELASTIC: a complete commit made at a different host
    count or mesh shape restores fine — targets are abstract arrays
    built from `state_like`'s current shardings, the manifest's recorded
    global tree is checked against them first (mismatches name the
    offending leaf), and `report` (optional out-param) receives
    `resume_mode` ("exact" | "resharded"), the saved topology and the
    restored step for the caller's heartbeat/metrics."""
    base = _abs(model_load_path)
    meta = verify_checkpoint(base, check_content=True)
    manifest = load_manifest(base)
    mode = classify_restore(manifest, config)
    if report is not None:
        report["resume_mode"] = mode
        report["path"] = base
        if manifest:
            report["saved_process_count"] = manifest.get("process_count")
            report["saved_mesh_plan"] = manifest.get("mesh_plan")
            report["data_cursor"] = manifest.get("data_cursor")
    if mode == "resharded":
        # Read-only by design: a kill anywhere in the reshard restore
        # must leave the artifact untouched and re-restorable (the
        # chaos matrix arms this point to prove it).
        fault_point("reshard_restore")
        obs.counter("resume_resharded_restores_total",
                    "restores that rebuilt the arrays for a topology "
                    "other than the save-time one").inc()
    if params_only:
        template = {"params": state_like.params, "step": state_like.step}
        _check_param_tree(manifest, template, base)
        restore_args = ocp.checkpoint_utils.construct_restore_args(template)
        try:
            restore = ocp.args.PyTreeRestore(item=template,
                                             restore_args=restore_args,
                                             partial_restore=True)
        except TypeError:
            # orbax < 0.6 has no partial_restore kwarg; empty `transforms`
            # is that vintage's way to restore a subtree of the saved item
            # (drop the artifact's opt_state, keep params+step).
            restore = ocp.args.PyTreeRestore(item=template,
                                             restore_args=restore_args,
                                             transforms={})
        with ocp.PyTreeCheckpointer() as ckptr:
            restored = ckptr.restore(os.path.join(base, _STATE_DIR),
                                     args=restore)
        if report is not None:
            report["restored_step"] = int(np.asarray(restored["step"]))
        return TrainState(step=restored["step"], params=restored["params"],
                          opt_state=state_like.opt_state)
    if config is not None and not meta.get("released", False):
        saved_sparse = bool(meta.get("use_sparse_embedding_update", False))
        want_sparse = bool(getattr(config, "use_sparse_embedding_update",
                                   False))
        if saved_sparse != want_sparse:
            raise ValueError(
                f"{base} was saved with use_sparse_embedding_update="
                f"{saved_sparse} but this run has "
                f"use_sparse_embedding_update={want_sparse}; the optimizer "
                f"state layouts are incompatible. Either set the flag to "
                f"match, or `--release` the artifact first (a released "
                f"model carries no optimizer state and loads under either "
                f"mode).")
        for knob in ("adam_mu_dtype", "adam_nu_dtype"):
            saved = meta.get(knob)
            want = str(getattr(config, knob, "float32"))
            # artifacts predating this meta entry carry no record (the
            # default changed over time) — nothing to check against
            if saved is not None and saved != want:
                raise ValueError(
                    f"{base} was saved with {knob}={saved} but this run "
                    f"has {knob}={want}; the optimizer-moment dtypes "
                    f"differ and a restore would corrupt or miscast the "
                    f"moments. Pass --{knob} {saved} to resume this "
                    f"artifact, or `--release` it first (released models "
                    f"carry no optimizer state).")
    template = {"params": state_like.params, "step": state_like.step}
    if not meta.get("released", False):
        template["opt_state"] = state_like.opt_state
    _check_param_tree(manifest, template, base)
    ckptr = ocp.StandardCheckpointer()
    restored = ckptr.restore(os.path.join(base, _STATE_DIR),
                             _abstract_restore_template(template))
    ckptr.close()
    if report is not None:
        report["restored_step"] = int(np.asarray(restored["step"]))
    return TrainState(
        step=restored["step"],
        params=restored["params"],
        opt_state=restored.get("opt_state", state_like.opt_state))


def release_model(model_load_path: str, model_save_path: Optional[str],
                  state_like: TrainState, vocabs, config) -> str:
    """Load a trainable artifact and re-save it weights-only
    (reference: tensorflow_model.py:131-135 saves `<load>.release`).
    Loads params-only: releasing discards the optimizer state, so a
    saved-vs-current optimizer layout/dtype mismatch must not block it."""
    state = load_model(model_load_path, state_like, params_only=True)
    out = model_save_path or model_load_path
    return save_model(out, state, vocabs, config, released=True)


# ------------------------------------------------- parameters-only artifacts

PARAMS_FORMAT = "params-v1"
_PARAMS_DIR = "params"


def save_params(model_save_path: str, params: dict, meta: dict) -> str:
    """Save a flat dict of parameter arrays, and nothing else, as an
    artifact `--load` accepts: one `.npy` file a leaf under `params/`
    (bfloat16 as its 16 bits), and the meta file with the leaf table,
    written last inside a staging directory that is then renamed into
    place (whole or absent, as `save_model` commits).

    Leaf by leaf, device to host to disk: the host never holds more
    than one leaf, so a model that fills the chip can be saved."""
    base = _abs(model_save_path)
    staging = f"{base}{STAGING_INFIX}{os.getpid()}"
    if os.path.isdir(staging):
        shutil.rmtree(staging)
    os.makedirs(os.path.join(staging, _PARAMS_DIR))
    table = {}
    with obs.span("checkpoint_save"):
        for name, leaf in params.items():
            host = np.asarray(leaf)
            dtype = str(leaf.dtype)
            if dtype == "bfloat16":
                host = host.view(np.uint16)
            np.save(os.path.join(staging, _PARAMS_DIR, name + ".npy"), host)
            table[name] = {"shape": list(leaf.shape), "dtype": dtype}
            del host
        with open(os.path.join(staging, _META_NAME), "w") as f:
            json.dump(dict(meta, format=PARAMS_FORMAT, released=True,
                           leaves=table), f)
    _commit_staging(staging, base)
    return base


def restore_params(model_load_path: str, abstract: dict) -> dict:
    """Restore a `save_params` artifact STRAIGHT into place: each leaf is
    read from its file and put on the device before the next is opened.
    No initial state is built first and nothing is held twice, so the
    device's peak is the parameters themselves (`load_model` restores
    into a whole fresh state: two states at the peak, which a model that
    fills the chip cannot afford). `abstract` maps each leaf's name to
    its expected shape and dtype; a leaf that is absent or differs is an
    error that names it."""
    import ml_dtypes
    base = _abs(model_load_path)
    meta = load_model_meta(base)
    if meta.get("format") != PARAMS_FORMAT:
        raise ValueError(f"{base} is not a parameters-only artifact "
                         f"(format {meta.get('format')!r})")
    table = meta["leaves"]
    missing = sorted(set(abstract) - set(table))
    if missing:
        raise ValueError(f"{base} lacks the leaves {missing[:5]} "
                         f"({len(missing)} in all)")
    out = {}
    for name, want in abstract.items():
        got = table[name]
        if (tuple(got["shape"]) != tuple(want.shape)
                or got["dtype"] != str(want.dtype)):
            raise ValueError(
                f"{base}: leaf {name} is {got['dtype']}{got['shape']}, "
                f"the model wants {want.dtype}{list(want.shape)}")
        host = np.load(os.path.join(base, _PARAMS_DIR, name + ".npy"),
                       mmap_mode="r")
        if got["dtype"] == "bfloat16":
            host = host.view(ml_dtypes.bfloat16)
        out[name] = jax.block_until_ready(jax.device_put(host))
        del host
    return out
