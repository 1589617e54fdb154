"""Orbax-backed checkpointing with preemption-safe semantics.

Closes a real gap in the reference: its jobs had no resume path at all —
checkpoints lived on a pod-local emptyDir synced to S3, and a restarted pod
started from scratch (SURVEY.md §5.4).  Here: every save is atomic (Orbax
renames on commit), the latest step is discoverable, and restore re-shards
onto the current mesh via device_put with the trainer's shardings.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import TYPE_CHECKING, Any, Callable

import jax

if TYPE_CHECKING:
    import orbax.checkpoint as ocp

logger = logging.getLogger(__name__)

_STEP_RE = re.compile(r"^step_(\d+)$")
#: uncommitted save staging: ``_save_msgpack`` writes ``step_N.tmp`` then
#: renames; Orbax stages ``step_N.orbax-checkpoint-tmp-<ts>``; the manifest
#: writer stages ``step_N.manifest.tmp`` — a SIGKILL mid-save strands any of
#: these (observed in the chaos tests), and the strays match the
#: artifact-sync globs, shipping garbage with every sync
_TMP_RE = re.compile(
    r"^step_\d+(\.tmp|\.manifest\.tmp|\.orbax-checkpoint-tmp-.*)$"
)

MANIFEST_NAME = "manifest.json"


def _shape_desc(node: object) -> str:
    if isinstance(node, dict):
        return "a subtree"
    shape = tuple(getattr(node, "shape", ()) or ())
    return f"shape {shape}"


class CheckpointShapeError(ValueError):
    """A restore target (``like`` tree) does not match the checkpoint.

    Raised BEFORE deserialization with the first offending leaf path and
    both shapes — the alternative is a raw msgpack/XLA error from deep
    inside the stack that names neither."""

    def __init__(self, path: str, ckpt: object, like: object):
        self.path = path
        super().__init__(
            f"checkpoint/template mismatch at {path!r}: checkpoint has "
            f"{ckpt}, restore template has {like} — wrong model config or "
            "training mode for this checkpoint"
        )


class CheckpointManager:
    """Saves are ASYNC by default: ``save`` hands the (already host-side)
    tree to a background writer and returns, so serialization + disk IO
    overlap the next training steps — the standard TPU goodput lever.  At
    most one save is in flight; ``wait()`` (called automatically before the
    next save, any read, and by the trainer's exit path) is the durability
    barrier."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = os.path.abspath(directory)
        self.keep = keep
        os.makedirs(self.directory, exist_ok=True)
        self._sweep_stale_tmp()
        self._ckptr_obj: ocp.StandardCheckpointer | None = None
        #: what the first ``_ckptr`` paid to import the checkpoint library,
        #: and the thread that paid it (None until a save or restore asks)
        self.backend_import_s: float | None = None
        self.backend_import_thread: str | None = None
        self._pending: threading.Thread | None = None
        self._pending_error: list[BaseException] = []

    @property
    def _ckptr(self) -> ocp.StandardCheckpointer:
        """Orbax's checkpointer, imported and built on the first save or
        restore, by the thread that makes it (a save's writer thread, so the
        import runs beside the training steps): the library's import scans
        every installed distribution twice (``google.cloud.logging``, ~12 s
        on a chip's host) and its constructor starts the JAX backend, and a
        manager that only lists steps — the API server staging a promoted
        checkpoint for its worker processes, a fresh job's ``latest_step()``
        — must neither pay the scans nor take the chip.  Nothing on a saving
        caller's thread may touch this before the writer does (``wait()``
        reads ``_ckptr_obj``)."""
        if self._ckptr_obj is None:
            t0 = time.perf_counter()
            import orbax.checkpoint as ocp

            self.backend_import_s = time.perf_counter() - t0
            self.backend_import_thread = threading.current_thread().name
            self._ckptr_obj = ocp.StandardCheckpointer()
        return self._ckptr_obj

    def take_backend_import(self) -> dict[str, Any]:
        """The library's import as span or event attributes, reported ONCE:
        its seconds and the thread that paid them to the first caller after
        the import, 0.0 and no thread before it and ever after."""
        seconds, self.backend_import_s = self.backend_import_s, None
        thread, self.backend_import_thread = self.backend_import_thread, None
        return {
            "backend_import_s": round(seconds or 0.0, 4),
            "backend_import_thread": thread,
        }

    def _sweep_stale_tmp(self) -> None:
        """Remove uncommitted ``step_N.tmp`` staging dirs left by a crash.

        A kill between ``_save_msgpack``'s makedirs and its atomic
        ``os.replace`` strands the staging dir forever: it is never a
        committed step (``_committed_steps`` ignores it) but it shadows the
        path of a FUTURE save of the same step — and it silently leaks disk
        on every crash.  Init is the safe sweep point: this manager is the
        directory's single writer and no save is in flight yet.
        """
        import shutil

        for name in os.listdir(self.directory):
            if not _TMP_RE.match(name):
                continue
            path = os.path.join(self.directory, name)
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            else:
                try:
                    os.remove(path)
                except OSError:
                    logger.warning("could not remove stale staging %s", name)
            logger.warning("swept stale uncommitted checkpoint staging %s", name)

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}")

    def wait(self) -> None:
        """Block until any in-flight save is committed to disk.

        Re-raises a background save's exception — a swallowed disk-full here
        would let a preempted job exit believing its checkpoint committed."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._ckptr_obj is not None:
            self._ckptr_obj.wait_until_finished()
        if self._pending_error:
            err = self._pending_error.pop()
            raise RuntimeError(f"background checkpoint save failed: {err}") from err

    def _committed_steps(self) -> list[int]:
        """Step dirs already committed on disk (does NOT wait — an in-flight
        save's dir only appears at its atomic rename)."""
        steps = []
        for name in os.listdir(self.directory):
            m = _STEP_RE.match(name)
            if m and os.path.exists(os.path.join(self.directory, name)):
                steps.append(int(m.group(1)))
        return sorted(steps)

    def all_steps(self) -> list[int]:
        self.wait()
        return self._committed_steps()

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _save_sync(
        self,
        path: str,
        tree: Any,
        manifest: dict | None,
        on_commit: Callable[[], None] | None,
    ) -> None:
        try:
            if jax.process_count() > 1:
                # Orbax's save is itself a cross-process collective
                # (sync_global_processes barriers); on multi-host only rank 0
                # calls save with an already-gathered host tree, so use a
                # non-collective msgpack writer (atomic tmp-dir rename — the
                # manifest rides inside the staging dir, so commit is atomic
                # for both).
                self._save_msgpack(path, tree, manifest)
            else:
                self._ckptr.save(path, tree)
                self._ckptr.wait_until_finished()
                if manifest is not None:
                    self._write_manifest(path, manifest)
            if on_commit is not None:
                on_commit()
        except BaseException as exc:  # noqa: BLE001 — re-raised from wait()
            logger.exception("background checkpoint save to %s failed", path)
            # ftc: ignore[shared-mutable-without-lock] -- single in-flight writer thread (save() waits before starting another); list.append is GIL-atomic and drained only after join() in wait()
            self._pending_error.append(exc)

    def save(
        self,
        step: int,
        tree: Any,
        force: bool = False,
        blocking: bool = False,
        manifest: dict | None = None,
        on_commit: Callable[[], None] | None = None,
    ) -> None:
        """``on_commit`` is called by the writer thread once this save is on
        disk (never for a save that failed, or that found its step there)."""
        self.wait()  # one in-flight save at a time (raises on a prior failure)
        path = self._path(step)
        if os.path.exists(path):
            if not force:
                return
            import shutil

            shutil.rmtree(path)
        # gc BEFORE starting the writer: gc lists only committed dirs, so it
        # must not (and does not) wait on the save we are about to start —
        # the whole point is overlapping serialization + IO with training
        self._gc()
        self._pending = threading.Thread(
            target=self._save_sync, args=(path, tree, manifest, on_commit),
            name="checkpoint-writer", daemon=False,
        )
        self._pending.start()
        if blocking:
            self.wait()

    @staticmethod
    def _save_msgpack(path: str, tree: Any, manifest: dict | None = None) -> None:
        import json

        from flax import serialization

        tmp = path + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        with open(os.path.join(tmp, "state.msgpack"), "wb") as f:
            f.write(serialization.to_bytes(tree))
        if manifest is not None:
            with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
                json.dump(manifest, f)
        os.replace(tmp, path)

    def _write_manifest(self, path: str, manifest: dict) -> None:
        """Stage-and-rename the manifest into an already-committed step dir
        (the Orbax path commits the tree itself, so the manifest lands right
        after; a kill in the gap leaves a manifest-less checkpoint, which
        restore treats as legacy, and the ``.manifest.tmp`` stray is swept
        at the next init)."""
        import json

        tmp = path + ".manifest.tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, os.path.join(path, MANIFEST_NAME))

    def load_manifest(self, step: int) -> dict | None:
        """The step's ``manifest.json`` (``train/elastic.py`` schema), or
        None for a pre-manifest (legacy) checkpoint."""
        import json

        self.wait()
        path = os.path.join(self._path(step), MANIFEST_NAME)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    @staticmethod
    def _validate_like(path_prefix: str, ckpt_node: Any, like_node: Any) -> None:
        """Walk checkpoint/template state-dicts together; raise
        :class:`CheckpointShapeError` at the first structural or shape
        mismatch instead of letting msgpack/XLA fail opaquely later."""
        ckpt_is_map = isinstance(ckpt_node, dict)
        like_is_map = isinstance(like_node, dict)
        if ckpt_is_map != like_is_map:
            raise CheckpointShapeError(
                path_prefix or "<root>",
                "a subtree" if ckpt_is_map else _shape_desc(ckpt_node),
                "a subtree" if like_is_map else _shape_desc(like_node),
            )
        if not ckpt_is_map:
            cs = tuple(getattr(ckpt_node, "shape", ()) or ())
            ls = tuple(getattr(like_node, "shape", ()) or ())
            if cs != ls:
                raise CheckpointShapeError(
                    path_prefix or "<root>", f"shape {cs}", f"shape {ls}"
                )
            return
        for key in sorted(set(ckpt_node) | set(like_node)):
            sub = f"{path_prefix}/{key}" if path_prefix else str(key)
            if key not in ckpt_node:
                raise CheckpointShapeError(sub, "<missing>", _shape_desc(like_node[key]))
            if key not in like_node:
                raise CheckpointShapeError(sub, _shape_desc(ckpt_node[key]), "<missing>")
            CheckpointManager._validate_like(sub, ckpt_node[key], like_node[key])

    def _validate_manifest_like(self, step: int, like: Any) -> bool:
        """Validate ``like`` against the step's manifest leaf map; returns
        False when no manifest exists (legacy checkpoint)."""
        manifest = self.load_manifest(step)
        leaves = (manifest or {}).get("leaves")
        if not leaves:
            return False
        from .elastic import leaf_entries

        like_leaves = leaf_entries(like)
        for path in sorted(set(leaves) | set(like_leaves)):
            if path not in leaves:
                raise CheckpointShapeError(
                    path, "<missing>", f"shape {tuple(like_leaves[path]['shape'])}"
                )
            if path not in like_leaves:
                raise CheckpointShapeError(
                    path, f"shape {tuple(leaves[path]['shape'])}", "<missing>"
                )
            cs = tuple(leaves[path]["shape"])
            ls = tuple(like_leaves[path]["shape"])
            if cs != ls:
                raise CheckpointShapeError(path, f"shape {cs}", f"shape {ls}")
        return True

    def restore(self, step: int, like: Any | None = None) -> Any:
        self.wait()
        path = self._path(step)
        if like is not None:
            self._validate_manifest_like(step, like)
        msgpack_file = os.path.join(path, "state.msgpack")
        if os.path.exists(msgpack_file):
            from flax import serialization

            with open(msgpack_file, "rb") as f:
                data = f.read()
            if like is None:
                return serialization.msgpack_restore(data)
            # validate against the raw bytes too (covers manifest-less
            # checkpoints): a mismatched template must name the leaf, not
            # die in from_bytes with a msgpack structure error
            raw = serialization.msgpack_restore(data)
            self._validate_like("", raw, serialization.to_state_dict(like))
            return serialization.from_state_dict(like, raw)
        return self._ckptr.restore(path, target=like)

    def restore_latest(self, like: Any | None = None) -> tuple[int, Any] | None:
        step = self.latest_step()
        if step is None:
            return None
        return step, self.restore(step, like)

    def _gc(self) -> None:
        steps = self._committed_steps()
        for step in steps[: -self.keep]:
            import shutil

            shutil.rmtree(self._path(step), ignore_errors=True)
            logger.info("gc'd checkpoint step_%d", step)


def reshard(tree: Any, shardings: Any) -> Any:
    """Place a host-restored tree onto devices with the given shardings."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)
