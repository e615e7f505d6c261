"""Sharded checkpointing with elastic restore and a versioned manifest.

Save path writes one .npy holding the GLOBAL array per pytree leaf, plus
a JSON manifest (schema v2: step, treedef, per-leaf key paths / top-level
sections / logical shapes and dtypes, and a caller-supplied ``meta``
dict). The restore path reassembles global arrays and `device_put`s them
under the *current* mesh's shardings -- so a checkpoint written on the
2-pod mesh restores onto a 1-pod mesh (elastic downscale) or a smoke
mesh (debug), which runtime/elastic.py relies on.

Restore is validating, never silently wrong: the saved treedef, leaf
count, per-leaf paths, and logical shapes are checked against the
example tree and a :class:`CheckpointError` with a readable diff is
raised on any mismatch (e.g. a cross-step carry present in the
checkpoint but ``cross_step_pipeline`` off at restore). Callers that
*intend* a partial restore select top-level ``sections`` explicitly --
that is how runtime/elastic.py drops a mesh-shaped carry instead of
`device_put`-ing stale partials.

Async mode snapshots to host then writes on a background thread so the
training loop is not blocked (the paper-style overlap discipline applied
to I/O).
"""
from __future__ import annotations

import json
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import ml_dtypes
import numpy as np
from jax.sharding import NamedSharding


# numpy cannot round-trip ml_dtypes (bf16 etc.) through np.save; store the
# raw bits and record the logical dtype in the manifest.
_BITCAST = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
            "float8_e5m2": np.uint8}

MANIFEST_VERSION = 2


class CheckpointError(ValueError):
    """A checkpoint/restore structure mismatch (never silently truncate,
    reorder, or mis-assign leaves)."""


def _keystr(kp) -> str:
    try:
        return jax.tree_util.keystr(kp)
    except Exception:  # pragma: no cover - ancient jax
        return "".join(str(k) for k in kp)


def _section_of(kp) -> str:
    """Top-level key of one leaf's key path ('params', 'opt', 'carry',
    ...) -- what section-filtered restores select on."""
    if not kp:
        return ""
    k = kp[0]
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _path_diff(expected: Sequence[str], saved: Sequence[str]) -> str:
    """Readable diff between the example tree's leaf paths and the
    checkpoint's: what the error message shows instead of a silent
    truncation or mis-assignment."""
    exp_set, sav_set = set(expected), set(saved)
    lines: List[str] = []
    missing = [p for p in expected if p not in sav_set]
    unexpected = [p for p in saved if p not in exp_set]
    if missing:
        lines.append("  leaves expected by the example tree but absent "
                     "from the checkpoint:")
        lines += [f"    {p}" for p in missing[:8]]
        if len(missing) > 8:
            lines.append(f"    ... and {len(missing) - 8} more")
    if unexpected:
        lines.append("  leaves present in the checkpoint but not in the "
                     "example tree:")
        lines += [f"    {p}" for p in unexpected[:8]]
        if len(unexpected) > 8:
            lines.append(f"    ... and {len(unexpected) - 8} more")
    if not lines:  # same set, different order
        for i, (e, s) in enumerate(zip(expected, saved)):
            if e != s:
                lines.append(f"  first order mismatch at leaf {i}: "
                             f"example {e} vs checkpoint {s}")
                break
    return "\n".join(lines)


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._async_thread: Optional[threading.Thread] = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = True,
             meta: Optional[Dict[str, Any]] = None) -> Path:
        """tree: arbitrary pytree of jax arrays / scalars. ``meta`` is an
        arbitrary JSON-serializable dict recorded in the manifest (the
        restart driver stores the mesh signature and whether a
        cross-step carry section rides along)."""
        path_leaves, treedef = jax.tree.flatten_with_path(tree)
        # snapshot to host memory first (cheap, lets async write proceed
        # while the next step runs; also decouples the write from any
        # donation of the live buffers by the next compiled step)
        host_leaves = [np.asarray(jax.device_get(leaf))
                       for _, leaf in path_leaves]
        leaf_meta = []
        for (kp, _), arr in zip(path_leaves, host_leaves):
            leaf_meta.append({"path": _keystr(kp),
                              "section": _section_of(kp),
                              "shape": list(arr.shape),
                              "dtype": str(arr.dtype)})
        manifest = {"version": MANIFEST_VERSION, "step": step,
                    "treedef": str(treedef), "n_leaves": len(host_leaves),
                    "meta": dict(meta or {}), "leaves": leaf_meta}
        path = self.dir / f"step_{step:08d}"
        tmp = self.dir / f".tmp_step_{step:08d}"

        def write():
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            for i, arr in enumerate(host_leaves):
                logical = str(arr.dtype)
                if logical in _BITCAST:
                    arr = arr.view(_BITCAST[logical])
                np.save(tmp / f"leaf_{i:05d}.npy", arr)
            with open(tmp / "manifest.json", "w") as f:
                json.dump(manifest, f)
            if path.exists():
                shutil.rmtree(path)
            tmp.rename(path)          # atomic publish
            self._gc()

        if blocking:
            write()
        else:
            self.wait()
            self._async_thread = threading.Thread(target=write, daemon=True)
            self._async_thread.start()
        return path

    def wait(self):
        if self._async_thread is not None and self._async_thread.is_alive():
            self._async_thread.join()

    def _gc(self):
        steps = sorted(self.all_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    # -- restore -------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for p in self.dir.glob("step_*"):
            try:
                out.append(int(p.name.split("_")[1]))
            except (IndexError, ValueError):
                continue
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def manifest(self, step: int) -> Dict[str, Any]:
        """The saved manifest dict (v1 checkpoints lack 'version',
        'meta', and per-leaf 'path'/'section' entries)."""
        with open(self.dir / f"step_{step:08d}" / "manifest.json") as f:
            return json.load(f)

    def _validate(self, manifest: Dict[str, Any], example_tree: Any,
                  sections: Optional[Tuple[str, ...]]) -> List[int]:
        """Check the manifest against the example tree; return the
        manifest leaf indices to load, in example-tree order."""
        version = manifest.get("version", 1)
        saved_leaves = manifest.get("leaves", [])
        n_saved = manifest.get("n_leaves", len(saved_leaves))
        if sections is not None:
            if version < 2:
                raise CheckpointError(
                    "section-filtered restore needs a manifest v2 "
                    f"checkpoint (saved version: {version})")
            idxs = [i for i, l in enumerate(saved_leaves)
                    if l.get("section") in sections]
        else:
            idxs = list(range(n_saved))
        ex_path_leaves, ex_treedef = jax.tree.flatten_with_path(example_tree)
        ex_paths = [_keystr(kp) for kp, _ in ex_path_leaves]
        if version >= 2:
            saved_paths = [saved_leaves[i]["path"] for i in idxs]
            if saved_paths != ex_paths:
                scope = (f"sections {sections}" if sections is not None
                         else "the full tree")
                raise CheckpointError(
                    f"checkpoint structure does not match the example "
                    f"tree for {scope} ({len(saved_paths)} saved vs "
                    f"{len(ex_paths)} expected leaves):\n"
                    + _path_diff(ex_paths, saved_paths))
            if sections is None and manifest.get("treedef") not in (
                    None, str(ex_treedef)):
                raise CheckpointError(
                    "checkpoint treedef does not match the example tree "
                    "(same leaf paths, different container structure):\n"
                    f"  saved:    {manifest['treedef']}\n"
                    f"  expected: {ex_treedef}")
            # logical-shape validation (global shapes are mesh-invariant,
            # so this holds across elastic restores; a mismatch means the
            # leaf is mesh-shaped -- e.g. a cross-step carry partial)
            for p, (_, leaf) in zip(idxs, ex_path_leaves):
                want = getattr(leaf, "shape", None)
                got = tuple(saved_leaves[p]["shape"])
                if want is not None and tuple(want) != got:
                    raise CheckpointError(
                        f"leaf {saved_leaves[p]['path']} shape mismatch: "
                        f"checkpoint {got} vs example {tuple(want)} "
                        "(mesh-shaped leaf restored under a different "
                        "mesh?)")
        else:
            if len(idxs) != len(ex_paths):
                raise CheckpointError(
                    f"checkpoint has {len(idxs)} leaves but the example "
                    f"tree has {len(ex_paths)} -- refusing to truncate "
                    "or pad a v1 restore")
            # v1 manifests have no paths but do record shapes: a
            # same-count, different-shape tree must still fail here with
            # a readable error, not later as an opaque XLA mismatch
            for i, (_, leaf) in zip(idxs, ex_path_leaves):
                want = getattr(leaf, "shape", None)
                got = tuple(saved_leaves[i].get("shape", ())) \
                    if i < len(saved_leaves) else None
                if want is not None and got is not None \
                        and tuple(want) != got:
                    raise CheckpointError(
                        f"v1 checkpoint leaf {i} shape mismatch: "
                        f"checkpoint {got} vs example {tuple(want)}")
        return idxs

    def restore(self, step: int, example_tree: Any,
                shardings: Optional[Any] = None,
                sections: Optional[Tuple[str, ...]] = None) -> Any:
        """Restore into the structure of example_tree (leaves may be
        arrays or ShapeDtypeStructs -- only structure/shape is read).

        ``shardings`` (pytree of NamedSharding, aligned with
        example_tree) places arrays under the current mesh -- the
        elastic-remesh path. ``sections`` selects top-level keys of a
        dict-rooted checkpoint (e.g. ``("params", "opt")`` to drop a
        mesh-shaped carry); the example tree must then contain exactly
        those sections. Raises :class:`CheckpointError` on any
        structural mismatch."""
        path = self.dir / f"step_{step:08d}"
        manifest = self.manifest(step)
        idxs = self._validate(manifest, example_tree, sections)
        _, treedef = jax.tree.flatten(example_tree)
        saved_leaves = manifest.get("leaves", [])
        leaves = []
        for i in idxs:
            arr = np.load(path / f"leaf_{i:05d}.npy")
            logical = saved_leaves[i]["dtype"]
            if logical in _BITCAST:
                arr = arr.view(getattr(ml_dtypes, logical))
            leaves.append(arr)
        if shardings is not None:
            sh_leaves = jax.tree.leaves(
                shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
            if len(sh_leaves) != len(leaves):
                raise CheckpointError(
                    f"shardings tree has {len(sh_leaves)} leaves for "
                    f"{len(leaves)} data leaves -- a short shardings "
                    "tree would silently leave trailing leaves on "
                    "default placement; pass one NamedSharding per leaf "
                    "(tree-aligned with the example tree)")
            leaves = [jax.device_put(l, s) for l, s in zip(leaves, sh_leaves)]
        else:
            leaves = [jax.device_put(l) for l in leaves]
        return jax.tree.unflatten(treedef, leaves)
