"""Sharded, atomic, manifest-based checkpointing with elastic restore.

Layout (one directory per step):
    <dir>/step_000123/
        manifest.json        tree structure + array metadata + status
        shard_00000.npz      this host's array shards
    <dir>/LATEST             text file: last COMMITTED step directory

Design points for 1000+-node runs (emulated single-host here, but the
layout is per-host from the start):
  * atomicity: shards are written first, the manifest is written+fsynced
    last, then LATEST is atomically renamed — a crash mid-write can never
    yield a half-checkpoint that restore() would accept;
  * every host writes only its addressable shards (`host_shards`); restore
    reassembles from any number of shard files, so the restoring job may
    run on a DIFFERENT mesh/host count (elastic re-sharding: arrays are
    saved logically, resharding happens at device_put with the new mesh);
  * data-pipeline cursor and optimizer step ride in the manifest for exact
    resume;
  * async save: the array->numpy transfer happens on the caller thread but
    file IO can be deferred to a background thread (``async_save``).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

from .. import telemetry

SEP = "/"


class SimulatedCrash(RuntimeError):
    """Raised by :func:`save` at an injected crash point (``_crash_after``)
    — the fault-injection harness's stand-in for the process dying mid-
    checkpoint. Everything written so far stays on disk exactly as a real
    kill would leave it; nothing is cleaned up, and the commit protocol
    must make the partial state invisible to :func:`restore`."""

# npz cannot serialize ml_dtypes (bf16/fp8); store a bit-view + dtype tag
_VIEW_DTYPES = {
    "bfloat16": (ml_dtypes.bfloat16, np.uint16),
    "float8_e4m3fn": (ml_dtypes.float8_e4m3fn, np.uint8),
    "float8_e5m2": (ml_dtypes.float8_e5m2, np.uint8),
}


def _flatten(tree: Any) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = SEP.join(_path_str(p) for p in path)
        flat[key] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    return str(p)


def tree_structure_of(tree: Any):
    return jax.tree_util.tree_structure(tree)


def gather_leaf(leaf: Any) -> np.ndarray:
    """Device -> host gather of one checkpoint leaf. Mesh-sharded arrays
    (e.g. the dense RPQ group's (Q, N, N, K) state under MeshExecutor) are
    reassembled into their LOGICAL value here — the manifest stores logical
    arrays only, which is what makes a checkpoint written on one mesh
    restorable onto another mesh or onto a single device (the restorer's
    executor re-places them; see restore()'s `shardings`)."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        raise ValueError(
            "cannot checkpoint a non-fully-addressable array from one "
            "process; gather it (or checkpoint per-host shards) first"
        )
    return np.asarray(jax.device_get(leaf))


def save(
    directory: str,
    step: int,
    tree: Any,
    extra: Optional[Dict[str, Any]] = None,
    host_id: int = 0,
    _crash_after: Optional[str] = None,
) -> str:
    """Synchronous checkpoint of a pytree of (possibly sharded) arrays.

    ``_crash_after`` is a fault-injection hook (tests/chaos harness only):
    raise :class:`SimulatedCrash` after the named stage completes —
    ``"shards"`` (array files written, no manifest), ``"manifest"``
    (manifest fsync'd inside the tmp dir, commit rename not taken), or
    ``"rename"`` (step dir renamed, LATEST not swung). Every one of these
    partial states must leave :func:`latest_step_dir` pointing at the
    previous committed step — that is the atomicity contract the crash-mid-
    save hardening tests pin."""
    flat = _flatten(tree)
    step_dir = os.path.join(directory, f"step_{step:09d}")
    tmp_dir = step_dir + f".tmp.{host_id}"
    os.makedirs(tmp_dir, exist_ok=True)

    arrays = {}
    meta = {}
    for key, leaf in flat.items():
        arr = gather_leaf(leaf)
        dtype_name = str(arr.dtype)
        if dtype_name in _VIEW_DTYPES:
            arr = arr.view(_VIEW_DTYPES[dtype_name][1])
        arrays[key.replace(SEP, "__")] = arr
        meta[key] = {"shape": list(arr.shape), "dtype": dtype_name}
    np.savez(os.path.join(tmp_dir, f"shard_{host_id:05d}.npz"), **arrays)
    if _crash_after == "shards":
        raise SimulatedCrash(f"injected crash after shard write: {tmp_dir}")

    manifest = {
        "step": step,
        "arrays": meta,
        "extra": extra or {},
        "n_hosts": jax.process_count(),
        "status": "committed",
    }
    mpath = os.path.join(tmp_dir, "manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if _crash_after == "manifest":
        raise SimulatedCrash(
            f"injected crash after manifest, before commit: {tmp_dir}")
    # commit: rename tmp dir, then swing LATEST atomically
    if os.path.exists(step_dir):
        shutil.rmtree(step_dir)
    os.rename(tmp_dir, step_dir)
    if _crash_after == "rename":
        raise SimulatedCrash(
            f"injected crash after rename, before LATEST: {step_dir}")
    latest_tmp = os.path.join(directory, ".LATEST.tmp")
    with open(latest_tmp, "w") as f:
        f.write(os.path.basename(step_dir))
        f.flush()
        os.fsync(f.fileno())
    os.replace(latest_tmp, os.path.join(directory, "LATEST"))
    return step_dir


_pending: Dict[str, Tuple[threading.Thread, list]] = {}


def async_save(directory: str, step: int, tree: Any,
               extra: Optional[Dict[str, Any]] = None,
               _crash_after: Optional[str] = None) -> None:
    """Device->host transfer now; file IO on a background thread so the
    serving/train loop is not blocked (one in-flight save at a time).

    ``_crash_after`` rides through to :func:`save`; a
    :class:`SimulatedCrash` raised on the background thread is swallowed
    there — exactly like a real process kill between ``async_save`` and
    ``wait_pending``, the save just never commits and the partial tmp dir
    is left behind for the atomicity contract to neutralize. Any other
    failure of the save is re-raised by the next :func:`wait_pending` on
    ``directory`` (which every later ``async_save`` calls first)."""
    wait_pending(directory)
    host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
    errors: list = []
    nbytes = sum(x.nbytes for x in jax.tree.leaves(host_tree))
    batch_id = telemetry.current_batch()

    def _run() -> None:
        try:
            with telemetry.batch(batch_id), \
                    telemetry.span("checkpoint.write", nbytes):
                save(directory, step, host_tree, extra,
                     _crash_after=_crash_after)
        except SimulatedCrash:
            pass  # the "process" died mid-save; partial state stays on disk
        except Exception as e:  # thread boundary: handed to wait_pending
            errors.append(e)

    t = threading.Thread(target=_run)
    t.start()
    _pending[directory] = (t, errors)


def wait_pending(directory: str) -> None:
    """Join the in-flight :func:`async_save` on ``directory``, re-raising
    the exception its save failed with (a simulated crash excepted)."""
    entry = _pending.pop(directory, None)
    if entry is None:
        return
    t, errors = entry
    with telemetry.span("checkpoint.join"):
        t.join()
    if errors:
        raise errors[0]


def _is_committed(step_dir: str) -> bool:
    mpath = os.path.join(step_dir, "manifest.json")
    if not os.path.exists(mpath):
        return False
    try:
        with open(mpath) as f:
            return json.load(f).get("status") == "committed"
    except (OSError, ValueError):
        return False


def latest_step_dir(directory: str) -> Optional[str]:
    """The last PUBLISHED step directory, or None. Publication is the
    atomic LATEST swing: while LATEST resolves to a committed dir, that
    dir wins — a newer step dir whose save crashed after the rename but
    before the swing is complete on disk yet deliberately invisible, so
    the commit point stays one unambiguous instruction. Only a missing or
    dangling LATEST (e.g. a crash between an rmtree of a re-saved step
    and its rename) falls back to scanning for the highest committed
    ``step_*`` dir, so a partial checkpoint can never be returned and a
    sole surviving committed one can never be missed."""
    latest = os.path.join(directory, "LATEST")
    if os.path.exists(latest):
        with open(latest) as f:
            name = f.read().strip()
        step_dir = os.path.join(directory, name)
        if _is_committed(step_dir):
            return step_dir
    if not os.path.isdir(directory):
        return None
    for name in sorted(os.listdir(directory), reverse=True):
        # tmp dirs are "step_<n>.tmp.<host>" — excluded by NAME, not by
        # manifest status: a crash after the manifest fsync but before the
        # commit rename leaves a committed-looking manifest inside the tmp
        # dir, and that state must stay invisible
        if name.startswith("step_") and ".tmp" not in name:
            step_dir = os.path.join(directory, name)
            if _is_committed(step_dir):
                return step_dir
    return None


def manifest_extra(directory: str) -> Dict[str, Any]:
    """The `extra` metadata of the latest committed checkpoint WITHOUT
    restoring any arrays — e.g. to inspect the recorded live query set of a
    persistent-query service (`extra["dense"]["order"]`) before deciding
    what to re-register."""
    step_dir = latest_step_dir(directory)
    if step_dir is None:
        raise FileNotFoundError(f"no committed checkpoint under {directory}")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        return json.load(f)["extra"]


def restore(
    directory: str,
    like: Any,
    shardings: Any = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Restore the latest committed checkpoint into the structure of `like`.

    `shardings`: optional pytree (or single sharding) applied via device_put
    — this is where ELASTIC re-sharding happens: the checkpoint stores
    logical arrays, so restoring onto a different mesh shape just means
    different shardings here.

    `like` fixes the tree STRUCTURE and leaf dtypes only; leaf shapes come
    from the file. Restorers whose capacities legitimately differ from the
    writer's (e.g. a dense query group with a different bucketed-Q/K/label
    padding history) therefore get the writer's arrays back verbatim and
    re-pad them onto their own layout (engine.adopt_state).
    """
    step_dir = latest_step_dir(directory)
    if step_dir is None:
        raise FileNotFoundError(f"no committed checkpoint under {directory}")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("status") != "committed":
        raise IOError(f"checkpoint {step_dir} not committed")
    arrays: Dict[str, np.ndarray] = {}
    for fn in sorted(os.listdir(step_dir)):
        if fn.startswith("shard_") and fn.endswith(".npz"):
            with np.load(os.path.join(step_dir, fn)) as z:
                for k in z.files:
                    arrays[k.replace("__", SEP)] = z[k]

    flat_like = _flatten(like)
    missing = set(flat_like) - set(arrays)
    if missing:
        raise KeyError(f"checkpoint missing arrays: {sorted(missing)[:5]} ...")

    flat_shard = None
    if shardings is not None and not _is_single_sharding(shardings):
        flat_shard = _flatten(shardings)

    out_flat = {}
    meta = manifest["arrays"]
    for key, leaf in flat_like.items():
        arr = arrays[key]
        stored = meta.get(key, {}).get("dtype", str(arr.dtype))
        if stored in _VIEW_DTYPES:
            arr = arr.view(_VIEW_DTYPES[stored][0])
        want_dtype = leaf.dtype if hasattr(leaf, "dtype") else arr.dtype
        if str(want_dtype) != str(arr.dtype):
            arr = arr.astype(want_dtype)
        if flat_shard is not None:
            out_flat[key] = jax.device_put(arr, flat_shard[key])
        elif shardings is not None:
            out_flat[key] = jax.device_put(arr, shardings)
        else:
            out_flat[key] = jax.device_put(arr)
    # rebuild tree in `like`'s structure
    leaves_order = [
        SEP.join(_path_str(p) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(like)[0]
    ]
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), [out_flat[k] for k in leaves_order]
    )
    return tree, manifest["extra"]


def _is_single_sharding(s: Any) -> bool:
    return isinstance(s, jax.sharding.Sharding)


# ---------------------------------------------------------------------------
# Opaque-object leaves: python engine state (e.g. the reference RPQ engines'
# pointer trees) rides the same manifest/shard machinery as device arrays by
# serializing to a uint8 leaf. Restore sites pass `pickle_like()` as the
# `like` leaf (dtype uint8; stored shape wins at load).
# ---------------------------------------------------------------------------


def pickle_leaf(obj: Any) -> np.ndarray:
    """Serialize an arbitrary python object into a checkpointable array."""
    import pickle

    return np.frombuffer(pickle.dumps(obj), dtype=np.uint8)


def unpickle_leaf(arr: Any) -> Any:
    """Inverse of :func:`pickle_leaf` (accepts np or device arrays)."""
    import pickle

    return pickle.loads(np.asarray(arr).tobytes())


def pickle_like() -> np.ndarray:
    """A `like` placeholder for a pickled leaf (shape comes from the file)."""
    return np.zeros((0,), np.uint8)
