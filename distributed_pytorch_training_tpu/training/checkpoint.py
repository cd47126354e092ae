"""Checkpoint / resume — absent from the reference (no torch.save/load
anywhere; SURVEY.md §5 "Checkpoint/resume: Absent") but required for usable
multi-host training on preemptible TPU pods.

Orbax-backed: sharded writes, multi-host-safe (every process participates;
no rank-0 funnel). Only the array pytrees are persisted
(step/params/batch_stats/opt_state/grad_sync); `apply_fn`/`tx` are code,
reconstructed by the caller — restoring requires a template TrainState with
matching structure, which `train.py` always has before resume.

Integrity (resilience/): every save writes a per-checkpoint MANIFEST
(step + a tree digest over the finalized files: path, size, sha256) into
``<dir>/.manifests/<label>.json``, and ``restore_latest`` verifies the
manifest before trusting a checkpoint — a torn/truncated checkpoint (disk
truncation, a partial copy, an injected ``torn_ckpt`` chaos fault) is
SKIPPED with a loud log and the previous valid one restores instead of the
run crashing on it. Orbax's own atomic-rename commit already excludes
interrupted writes from ``all_steps``; the manifest covers the post-commit
corruption class orbax cannot see. Legacy checkpoints (written before
manifests existed) have no manifest and restore unverified, exactly as
before.

Async saves (snapshot-then-write): ``save`` used to finalize synchronously
so the manifest could hash final files — the measured step-time stall this
design kills. Now only the device→host SNAPSHOT happens on the caller's
thread (it must: the train step donates the state buffers, so deferring the
copy would read freed memory), and the orbax write + chunked-sha256 manifest
run on ONE background writer while training continues. Barriers:

* the next ``save`` joins the previous write first (at most one write in
  flight — also where a failed async write surfaces, as the raised error);
* ``wait()`` / ``close()`` at shutdown, and every restore/metadata read,
  join the writer before touching the directory.

The async window does NOT widen the torn-checkpoint window silently: a
PENDING marker (``.manifests/<label>.pending``) is written before the
background write starts and removed only after the manifest finalizes, so a
crash between the orbax commit and the manifest leaves a checkpoint that
``verify`` reports as torn ("never finalized") instead of one that
masquerades as a trusted legacy checkpoint. What async changes is *when*
bytes hit disk, never *what*: the written files and manifest digests are
those of a synchronous save of the same state (PARITY.md).

Blocked-time accounting (train.py's and the chaos CLI's end-of-run
line): ``save_blocked_ms`` sums every millisecond the calling thread spent
inside ``save``/``wait`` — under async saves it collapses to
~``snapshot_ms`` (the device→host copy), which is the whole point.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from pathlib import Path
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from ..utils.logging import log_main
from .. import telemetry
from .train_state import TrainState

_MANIFEST_DIRNAME = ".manifests"
_MANIFEST_FORMAT = 1


class CheckpointWorldSizeMismatch(RuntimeError):
    """A checkpoint written at one DP world size was restored against a
    template built for another — the flat-padded layouts (zero1 moments,
    fsdp params+moments, EF residuals) change shape with the shard count,
    so orbax's opaque tree-mismatch dump is really THIS error. Raised with
    both sizes in the message and the chosen candidate on the instance
    (``label`` / ``world_size`` — train.py's elastic-resume fallback
    restores exactly that label raw instead of re-scanning the
    directory); resolve by restoring through
    ``restore_latest(template_factory=...)`` (build the template at the
    checkpoint's recorded world size and reshard — resilience/elastic.py)
    or by resuming at the original world size."""

    label: Optional[int] = None
    world_size: Optional[int] = None


def _file_sha256(path: Path) -> str:
    # chunked: checkpoint data files are model-sized, and a whole-file
    # read_bytes() would spike host RAM by the checkpoint size on every
    # save/verify — on a host already holding params + optimizer state
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 22), b""):
            h.update(block)
    return h.hexdigest()


def _arrays(state: TrainState, epoch: int = 0, step_in_epoch: int = 0) -> dict:
    arrays = {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        # step-granular resume coordinates: the sampler is deterministic in
        # (seed, epoch), so (epoch, step_in_epoch) fully locates the
        # trajectory — a preemption at minute 50 no longer replays the
        # epoch. 0-d ndarrays, NOT numpy scalars: orbax's restore-template
        # validation rejects np.int32(0) (not in its supported leaf types).
        "epoch": np.asarray(epoch, np.int32),
        "step_in_epoch": np.asarray(step_in_epoch, np.int32),
    }
    # int8-wire error-feedback residuals (parallel/grad_sync.py): the
    # carried quantization remainder IS trajectory state — dropping it at
    # resume re-introduces the bias EF exists to cancel. Included only
    # when non-empty so every other mode's checkpoints keep the legacy
    # structure (resumable across this feature's introduction, both ways).
    import jax

    if jax.tree_util.tree_leaves(state.grad_sync):
        arrays["grad_sync"] = state.grad_sync
    return arrays


class CheckpointManager:
    """Step-granular save/restore-latest (the resume story the reference's
    append-only CSV hints at but never implements, ref :349-354).

    `label` orders checkpoints (use epoch * steps_per_epoch + step so
    mid-epoch preemption saves sort between epoch boundaries); the restored
    (epoch, step_in_epoch) pair tells the caller exactly where to resume.

    Layout-agnostic: restore lands every array in the TEMPLATE's sharding,
    so the flat-padded-sharded layouts (zero1's moments; fsdp_explicit's
    params + moments + per-group EF residuals) round-trip exactly as the
    replicated layout does — provided the template was built under the
    same mesh and mode flags (train.py's resume hint names them).

    ``async_save=True`` (the default) makes ``save`` snapshot-then-write:
    device→host copy on the caller's thread, orbax write + manifest on a
    background writer (``save(..., wait=True)`` forces one save back to
    synchronous — the preemption-drain saves use it: the process is about
    to exit, overlap buys nothing). A failed background write re-raises
    from the NEXT ``save``/``wait`` call — inside the supervisor's
    recovery scope, so "on a step/save failure, restore the latest valid
    checkpoint" covers async saves too.

    ``post_save_hook(label, step_dir)`` fires after a save (and its
    manifest) finalized — the chaos harness's torn-checkpoint injection
    point (resilience/faults.py). ``pre_finalize_hook(label)`` fires
    between the orbax commit and the manifest write — the
    ``crash_during_save`` injection point (a raise there aborts the save
    exactly inside the async window the pending marker guards).
    ``last_skipped`` lists the labels the most recent ``restore_latest``
    rejected on integrity (the supervisor's recovery report reads it)."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 post_save_hook: Optional[Callable[[int, Path], None]]
                 = None,
                 async_save: bool = True,
                 pre_finalize_hook: Optional[Callable[[int], None]] = None):
        self._dir = Path(directory).resolve()
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True),
        )
        self._post_save_hook = post_save_hook
        self._pre_finalize_hook = pre_finalize_hook
        self._async = bool(async_save)
        self.last_skipped: List[int] = []
        # label the most recent restore_latest actually restored (None
        # before any restore) — serving reads it to provenance the weights
        # it serves (which label, which manifest digest)
        self.last_restored: Optional[int] = None
        # labels already proven torn (label -> problem): a torn checkpoint
        # stays torn, so later restores must not re-hash its files to
        # rediscover it. Cleared per label on re-save.
        self._known_bad: dict = {}
        # the one in-flight background write (at most one: the next save
        # joins it first, so orbax manager state is never touched from two
        # threads at once) and its failure, surfaced at the next barrier.
        # Lock-free by protocol, not by accident: the writer thread writes
        # _writer_label/_writer_error, the caller reads them only AFTER
        # _join_writer's t.join() — the join IS the happens-before edge,
        # and the at-most-one-writer invariant means there is never a
        # second thread to race
        self._writer: Optional[threading.Thread] = None
        self._writer_label: Optional[int] = None
        self._writer_error: Optional[BaseException] = None
        # blocked-time accounting
        self.save_blocked_ms = 0.0   # caller-thread ms inside save()/wait()
        self.snapshot_ms = 0.0       # of which: the device→host snapshot
        self.saves_started = 0

    # -- manifest plumbing -------------------------------------------------

    def _step_dir(self, label: int) -> Path:
        return self._dir / str(label)

    def _manifest_path(self, label: int) -> Path:
        return self._dir / _MANIFEST_DIRNAME / f"{label}.json"

    def _pending_path(self, label: int) -> Path:
        return self._dir / _MANIFEST_DIRNAME / f"{label}.pending"

    @staticmethod
    def _shape_summary(snapshot: dict) -> dict:
        """Sorted per-subtree shape multisets of the state being saved —
        recorded in the manifest so a cross-world restore can detect a
        layout mismatch BEFORE orbax touches the arrays (orbax's own item
        metadata is not reliably readable across versions, and its
        StandardRestore silently TRUNCATES a flat-padded leaf into a
        smaller template instead of failing)."""
        out = {}
        for key in ("params", "opt_state", "grad_sync"):
            if key in snapshot:
                out[key] = sorted(
                    list(np.shape(leaf))
                    for leaf in jax.tree_util.tree_leaves(snapshot[key]))
        return out

    def _write_manifest(self, label: int, step: int,
                        world_size: Optional[int] = None,
                        shapes: Optional[dict] = None) -> None:
        step_dir = self._step_dir(label)
        files = {}
        tree = hashlib.sha256()
        for p in sorted(step_dir.rglob("*")):
            if not p.is_file():
                continue
            rel = p.relative_to(step_dir).as_posix()
            digest = _file_sha256(p)
            size = p.stat().st_size
            files[rel] = {"size": size, "sha256": digest}
            tree.update(f"{rel}\0{size}\0{digest}\0".encode())
        manifest = {"format": _MANIFEST_FORMAT, "label": label,
                    "step": int(step), "n_files": len(files),
                    "tree_digest": tree.hexdigest(), "files": files}
        if world_size is not None:
            # the DP world size (batch shards) the state was laid out for:
            # the per-label probe elastic restores / template factories use
            # to build a matching template (legacy manifests lack it)
            manifest["world_size"] = int(world_size)
        if shapes:
            manifest["shapes"] = shapes
        path = self._manifest_path(label)
        path.parent.mkdir(parents=True, exist_ok=True)
        # atomic: a manifest torn by a crash mid-write must read as invalid
        # (skip), never as a half-truth that validates a half-checkpoint
        tmp = path.with_suffix(".json.tmp")
        tmp.write_text(json.dumps(manifest, sort_keys=True))
        os.replace(tmp, path)
        # prune manifests (and pending markers) of steps orbax's
        # max_to_keep already deleted
        live = {str(s) for s in self._mgr.all_steps()}
        for stale in list(path.parent.glob("*.json")) \
                + list(path.parent.glob("*.pending")):
            if stale.stem not in live:
                stale.unlink(missing_ok=True)

    def verify(self, label: int) -> Optional[str]:
        """None = intact (or legacy: no manifest to check — restores
        unverified, exactly as before manifests existed); otherwise a
        human-readable description of the corruption. An orbax-committed
        step whose PENDING marker survives without a manifest is an async
        save that died before finalizing — torn, never legacy. Failures
        are cached per label (torn stays torn) so repeated restores under
        the restart supervisor don't re-hash the same dead checkpoint."""
        if label in self._known_bad:
            return self._known_bad[label]
        problem = self._verify_uncached(label)
        if problem is not None:
            self._known_bad[label] = problem
        return problem

    def _verify_uncached(self, label: int) -> Optional[str]:
        path = self._manifest_path(label)
        if not path.exists():
            if self._pending_path(label).exists():
                # the async writer started this save and never finalized it
                # (crash between the orbax commit and the manifest write) —
                # the files may even be complete, but nothing vouches for
                # them; treating it as legacy would silently WIDEN the
                # torn-checkpoint window by exactly the async interval
                return ("async save never finalized (pending marker "
                        "present, no manifest — the writer died between "
                        "the orbax commit and the manifest)")
            return None  # legacy checkpoint
        try:
            manifest = json.loads(path.read_text())
            files = manifest["files"]
        except Exception as e:
            return f"unreadable manifest ({e})"
        step_dir = self._step_dir(label)
        for rel, info in files.items():
            p = step_dir / rel
            if not p.is_file():
                return f"file {rel} missing"
            size = p.stat().st_size
            if size != info["size"]:
                return (f"file {rel} truncated ({size} bytes, manifest "
                        f"says {info['size']})")
            if _file_sha256(p) != info["sha256"]:
                return f"file {rel} corrupt (digest mismatch)"
        return None

    # -- the background writer ---------------------------------------------

    def _join_writer(self, reraise: bool = True) -> None:
        """Barrier on the in-flight write. ``reraise=True`` (save/wait)
        surfaces a failed write as the raised error — inside the
        supervisor's recovery scope; ``reraise=False`` (restore/metadata/
        close paths) logs it instead: a failed save is a torn/absent
        checkpoint, which the integrity verification already handles."""
        t = self._writer
        if t is not None:
            t.join()
            self._writer = None
        err, label = self._writer_error, self._writer_label
        if err is None:
            return
        self._writer_error = None
        self._writer_label = None
        if reraise:
            raise err
        log_main(f"CHECKPOINT: async save of checkpoint {label} failed "
                 f"({type(err).__name__}: {err}) — it will be skipped by "
                 "integrity verification")

    def _write_job(self, label: int, snapshot: dict, step_value: int,
                   world_size: Optional[int] = None) -> None:
        """Everything after the snapshot: orbax write + finalize, the
        manifest, the pending-marker removal, and the hooks. Runs on the
        writer thread (async) or inline (sync / ``wait=True``)."""
        self._mgr.save(label, args=ocp.args.StandardSave(snapshot))
        self._mgr.wait_until_finished()
        if self._pre_finalize_hook is not None:
            # the crash_during_save window: orbax has committed, the
            # manifest does not exist yet — a raise here must leave a
            # checkpoint restore_latest skips loudly (the pending marker)
            self._pre_finalize_hook(label)
        # manifest writes are process-0-only: every process hashing and
        # racing the same .manifests/<label>.json.tmp on shared storage
        # could publish interleaved JSON — an "unreadable manifest" that
        # makes a GOOD checkpoint skip forever. Verification stays on
        # every process (read-only; all reach the same verdict).
        if jax.process_index() == 0:
            self._write_manifest(label, step=step_value,
                                 world_size=world_size,
                                 shapes=self._shape_summary(snapshot))
            self._pending_path(label).unlink(missing_ok=True)
        if self._post_save_hook is not None:
            self._post_save_hook(label, self._step_dir(label))

    def _writer_main(self, label: int, snapshot: dict, step_value: int,
                     world_size: Optional[int] = None) -> None:
        try:
            self._write_job(label, snapshot, step_value,
                            world_size=world_size)
        except BaseException as e:  # surfaced at the next barrier
            self._writer_error = e
            self._writer_label = label

    # -- save / restore ----------------------------------------------------

    def save(self, label: int, state: TrainState, wait: bool = False,
             epoch: Optional[int] = None, step_in_epoch: int = 0,
             world_size: Optional[int] = None) -> None:
        """`epoch` defaults to `label` (the legacy epoch-granular callers
        label saves by completed-epoch count). Snapshot-then-write: the
        device→host copy happens HERE (the train step donates these
        buffers — deferring the read would race the donation), then the
        orbax write + manifest run on the background writer unless
        ``wait=True`` or the manager was built ``async_save=False``.
        Joins (and surfaces the failure of) any previous in-flight write
        first. Re-saving an existing label (the supervisor replaying over
        a torn save) replaces the whole step. ``world_size`` (the DP batch
        shard count the state is laid out for) is recorded in the manifest
        so cross-world restores — elastic resizes — can probe it per label
        (`checkpoint_world_size`) and build a matching template."""
        t0 = time.perf_counter()
        self._join_writer()
        if label in self._mgr.all_steps():
            # never mix a fresh save into a stale (possibly torn) step dir
            self._mgr.delete(label)
            self._manifest_path(label).unlink(missing_ok=True)
        self._known_bad.pop(label, None)
        t_snap = time.perf_counter()
        # the only device work of a save: one host copy of the arrays.
        # numpy leaves land in orbax exactly like device arrays do, so the
        # written bytes (and manifest digests) match a synchronous save.
        snapshot = jax.device_get(_arrays(
            state, label if epoch is None else epoch, step_in_epoch))
        step_value = int(snapshot["step"])
        self.snapshot_ms += (time.perf_counter() - t_snap) * 1e3
        self.saves_started += 1
        if jax.process_index() == 0:
            pending = self._pending_path(label)
            pending.parent.mkdir(parents=True, exist_ok=True)
            pending.write_text(json.dumps(
                {"label": label, "step": step_value}))
        if self._async and not wait:
            t = threading.Thread(
                target=self._writer_main,
                args=(label, snapshot, step_value, world_size),
                name=f"ckpt-writer-{label}", daemon=True)
            self._writer = t
            t.start()
        else:
            self._write_job(label, snapshot, step_value,
                            world_size=world_size)
        blocked_s = time.perf_counter() - t0
        self.save_blocked_ms += blocked_s * 1e3
        # the save_blocked telemetry span: exactly the caller-thread stall
        # this save cost the train loop (under async ≈ the snapshot copy)
        telemetry.span_event("save_blocked", blocked_s, label=label,
                             phase="save",
                             async_save=bool(self._async and not wait))

    def _template_shapes_differ(self, label: int,
                                template: TrainState) -> bool:
        """Whether the checkpoint's saved array shapes differ from the
        template's — compared as per-subtree shape MULTISETS, so the
        replicated layout (whose shapes are world-size independent)
        restores across worlds unharassed while a flat-padded layout's
        changed padding is caught. Shapes come from OUR manifest (the
        `shapes` field `_write_manifest` records) — orbax's item metadata
        is not reliably readable across versions, and this check is what
        stands between a cross-world restore and StandardRestore's silent
        truncation. False when no shape record exists (legacy manifest:
        the restore then proceeds on its own merits)."""
        manifest = self.manifest(label)
        saved = (manifest or {}).get("shapes")
        if not saved:
            return False

        def shapes(tree) -> List[list]:
            return sorted(
                list(np.shape(leaf))
                for leaf in jax.tree_util.tree_leaves(tree))

        try:
            # grad_sync is compared too: the replicated+int8 layout's
            # params/opt_state are world-independent — ONLY its (n, R)
            # EF residual rows change with the world, and orbax would
            # truncate them just as silently. (A cross-world restore that
            # ALSO toggles compression trips this check as well — that
            # combination has no supported restore path, and the named
            # error beats orbax's structure dump.)
            for key, want in saved.items():
                if shapes(getattr(template, key)) != sorted(
                        list(s) for s in want):
                    return True
        except Exception:
            return False
        return False

    def checkpoint_world_size(self, label: Optional[int]) -> Optional[int]:
        """The DP world size (batch shards) checkpoint ``label`` was saved
        under, from its manifest — None for legacy manifests (written
        before the field existed), manifest-less checkpoints, or a None
        label. The per-label probe elastic restores key their template
        (and reshard decision) on."""
        if label is None:
            return None
        manifest = self.manifest(label)
        if manifest is None:
            return None
        w = manifest.get("world_size")
        return int(w) if w is not None else None

    def _verified_labels(self, among=None):
        """Candidate labels, newest first, that PASS integrity
        verification — the shared front half of every restore: joins the
        writer, resets + records ``last_skipped``, logs each torn skip
        loudly. A generator so callers stop at the first hit."""
        self._join_writer(reraise=False)
        self.last_skipped = []
        labels = sorted((label for label in self._mgr.all_steps()
                         if among is None or label in among), reverse=True)
        for label in labels:
            problem = self.verify(label)
            if problem is not None:
                log_main(f"CHECKPOINT INTEGRITY: checkpoint {label} is "
                         f"torn ({problem}) — skipping it and trying the "
                         "previous one")
                telemetry.emit("event", "torn_checkpoint_skipped",
                               label=label, problem=problem)
                self.last_skipped.append(label)
                continue
            yield label

    def restore_latest_raw(
        self, among=None,
    ) -> Optional[Tuple[dict, int, Optional[int], int, int]]:
        """Newest VALID checkpoint as HOST numpy arrays in their SAVED
        shapes — no template. Returns ``(arrays, label, world_size,
        epoch, step_in_epoch)`` or None; torn checkpoints are skipped
        exactly as in :meth:`restore_latest`.

        The cross-PROCESS elastic restore (ISSUE 12): a fleet relaunch at
        a different world size cannot build the old world's device
        templates (that mesh no longer exists in this process), so the
        checkpoint's own saved shapes stand in for the template and the
        caller reshards the host arrays into its current layout
        (``resilience.elastic.reshard_raw_state``). Orbax reconstructs
        the saved pytree as plain nested containers whose flattened leaf
        order mirrors the saved TrainState's (both sides flatten the same
        structure), so positional re-unflattening onto a matching
        template treedef is exact — the reshard's per-leaf shape checks
        catch a structural drift loudly."""
        for label in self._verified_labels(among):
            with telemetry.span("restore", label=label, raw=True):
                restored = self._mgr.restore(
                    label, args=ocp.args.StandardRestore())
            self.last_restored = label
            return (restored, label, self.checkpoint_world_size(label),
                    int(restored["epoch"]), int(restored["step_in_epoch"]))
        if self.last_skipped:
            log_main(f"CHECKPOINT INTEGRITY: every checkpoint "
                     f"({self.last_skipped}) failed verification — "
                     "nothing to restore")
        return None

    def restore_latest(
        self, template: Optional[TrainState] = None, among=None,
        template_factory=None, template_world_size: Optional[int] = None,
    ) -> Optional[Tuple[TrainState, int, int]]:
        """Returns (state, epoch, step_in_epoch) from the newest checkpoint
        that PASSES integrity verification, or None if none exists (torn
        ones are skipped with a loud log — recorded in ``last_skipped``).
        `template` supplies structure/sharding for every restored array.
        step_in_epoch > 0 means the save was a mid-epoch preemption:
        resume epoch `epoch` AT that step (the loaders' start_step).
        ``among`` (a collection of labels) restricts the candidates — the
        restart supervisor of a NON-resume run passes the labels it wrote
        itself, so a stale checkpoint a previous run left in the same
        directory can never leak into a fresh trajectory. Any in-flight
        async write is joined first (a restore must never race the
        writer); its failure, if any, is logged, not raised — a failed
        save is exactly a torn checkpoint, handled below.

        World sizes: ``template_factory(world)`` (instead of ``template``)
        builds the template PER CANDIDATE from the manifest's recorded
        world size (None for legacy manifests) — the elastic-restore path:
        a checkpoint written at 8 replicas restores into an 8-world
        template even when the run now holds 4 (the caller reshards,
        resilience/elastic.py). With a plain ``template``,
        ``template_world_size`` turns orbax's opaque structure-mismatch
        dump into :class:`CheckpointWorldSizeMismatch` naming both sizes
        whenever the manifest proves the worlds really differ."""
        if (template is None) == (template_factory is None):
            raise ValueError("restore_latest needs exactly one of "
                             "`template` or `template_factory`")
        for label in self._verified_labels(among):
            saved_world = self.checkpoint_world_size(label)
            if template_factory is not None:
                tmpl = template_factory(saved_world)
            else:
                tmpl = template
                if (saved_world is not None
                        and template_world_size is not None
                        and saved_world != template_world_size
                        and self._template_shapes_differ(label, tmpl)):
                    # MUST be checked before the restore: orbax does not
                    # reliably reject a shape mismatch — StandardRestore
                    # can silently truncate a flat-padded leaf into the
                    # smaller-world template, which corrupts the state
                    # instead of failing
                    err = CheckpointWorldSizeMismatch(
                        f"checkpoint {label} was written at world size "
                        f"{saved_world} (DP batch shards), but the "
                        "restore template was built for world size "
                        f"{template_world_size} — flat-padded layouts "
                        "(zero1 moments, fsdp params, EF residuals) "
                        "change shape with the DP degree. Restore with a "
                        f"template built at world size {saved_world} "
                        "(restore_latest(template_factory=...)) and "
                        "reshard via resilience.elastic, or resume at "
                        "the original world size")
                    # the already-verified, already-chosen candidate: an
                    # elastic-resume fallback restores exactly this label
                    # (among={err.label}) instead of re-scanning — and
                    # re-hashing — every candidate from scratch
                    err.label = label
                    err.world_size = saved_world
                    raise err
            return self._restore(label, tmpl)
        if self.last_skipped:
            log_main(f"CHECKPOINT INTEGRITY: every checkpoint "
                     f"({self.last_skipped}) failed verification — "
                     "nothing to restore")
        return None

    def manifest(self, label: int) -> Optional[dict]:
        """The integrity manifest of one checkpoint (``tree_digest``,
        per-file sizes/sha256), or None for a legacy (pre-manifest)
        checkpoint / unreadable manifest. The serving engine embeds the
        ``tree_digest`` in its provenance record: a served model names the
        exact bytes it serves."""
        self._join_writer(reraise=False)
        path = self._manifest_path(label)
        if not path.exists():
            return None
        try:
            return json.loads(path.read_text())
        except Exception:
            return None

    def _restore(self, label: int,
                 template: TrainState) -> Tuple[TrainState, int, int]:
        with telemetry.span("restore", label=label):
            out = self._restore_inner(label, template)
        # only a restore that SUCCEEDED may claim the label (a template
        # mismatch raises above — provenance must not name it)
        self.last_restored = label
        return out

    def _restore_inner(self, label: int,
                       template: TrainState) -> Tuple[TrainState, int, int]:
        want = _arrays(template)
        if "grad_sync" in want:
            # An int8-wire template resuming a checkpoint written WITHOUT
            # EF residuals (pre-feature, or the flag was just turned on):
            # orbax rejects a template key the checkpoint lacks outright,
            # so drop it and let the .get below keep the template's
            # zero-initialized residuals — error feedback restarts its
            # telescope from zero, which is exactly a fresh-start step.
            meta = self.metadata(label)
            if meta is not None and "grad_sync" not in meta:
                want.pop("grad_sync")
        restored = self._mgr.restore(
            label, args=ocp.args.StandardRestore(want))
        state = template.replace(
            step=restored["step"],
            params=restored["params"],
            batch_stats=restored["batch_stats"],
            opt_state=restored["opt_state"],
            # .get: checkpoints written before grad_sync existed restore
            # into non-EF templates (grad_sync={}) unchanged
            grad_sync=restored.get("grad_sync", template.grad_sync),
        )
        return state, int(restored["epoch"]), int(restored["step_in_epoch"])

    def metadata(self, label: Optional[int] = None) -> Optional[dict]:
        """Structure/shape metadata of one checkpoint (default: latest)
        WITHOUT reading array data (orbax item metadata). Lets callers
        diagnose a template mismatch precisely — e.g. a TP-vocab-padded
        (50304, d) embedding saved under a different --mesh than the
        resume run's."""
        self._join_writer(reraise=False)
        if label is None:
            label = self._mgr.latest_step()
        if label is None:
            return None
        try:
            return self._mgr.item_metadata(label)
        except Exception:
            return None

    def latest_metadata(self) -> Optional[dict]:
        return self.metadata()

    def wait(self) -> None:
        """Barrier: join the background writer (re-raising its failure —
        a shutdown must not silently drop a lost save) and drain orbax."""
        t0 = time.perf_counter()
        try:
            self._join_writer()
            self._mgr.wait_until_finished()
        finally:
            blocked_s = time.perf_counter() - t0
            self.save_blocked_ms += blocked_s * 1e3
            telemetry.span_event("save_blocked", blocked_s, phase="wait")

    def close(self) -> None:
        self._join_writer(reraise=False)
        self._mgr.close()
