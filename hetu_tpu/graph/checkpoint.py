"""Sharded checkpointing (orbax-backed).

The reference pickles name→numpy on rank 0 and PS-resident params via
SaveParam RPCs (executor.py:558-670).  `Executor.save/load` keeps that
single-file contract (plus RNG state for bitwise resume); this module adds
the multi-host path: each host writes only its addressable shards and
restores straight into the live sharding layout, which is how TPU-pod
checkpoints must work (a 100B-param state never materializes on one host).
"""

from __future__ import annotations

import logging
import os
import pickle
import re

import numpy as np

import jax
import jax.numpy as jnp


class CheckpointError(RuntimeError):
    """A checkpoint file or payload is torn, corrupt, or structurally
    invalid.  Raised instead of the opaque ``KeyError``/unpickle crash a
    garbage or stale file used to produce, so callers (and the rolling
    checkpoint manager's fallback scan) can tell "bad file" from "bug"."""


class GeometryMismatch(CheckpointError):
    """A checkpoint was written under a different geometry (mesh shape /
    per-param shardings) than the live executor's — a same-geometry
    restore would die inside orbax with a shape or topology error, so
    the mismatch is raised up front with BOTH geometries named.  Use
    :func:`restore_resharded` (or ``restore_latest(...,
    reshard=True)``) when the cross-geometry load is intended."""

    def __init__(self, message, saved=None, live=None):
        super().__init__(message)
        self.saved = saved
        self.live = live


# the single-file checkpoint contract (Executor.state_dict); "format" /
# "opt_meta" are optional so pre-tag checkpoints keep loading
REQUIRED_STATE_KEYS = frozenset(
    {"params", "opt_state", "global_step", "base_key"})
SUPPORTED_FORMAT_VERSIONS = (1,)


def validate_state(state, source="checkpoint"):
    """Check a checkpoint payload against the state_dict contract.

    Raises :class:`CheckpointError` naming exactly what is wrong
    (non-dict payload, missing required keys, format version from a
    newer writer) instead of letting ``load_state_dict`` die on an
    arbitrary ``KeyError`` deep inside the restore."""
    if not isinstance(state, dict):
        raise CheckpointError(
            f"{source}: payload is {type(state).__name__}, expected the "
            "dict produced by Executor.state_dict()")
    missing = sorted(REQUIRED_STATE_KEYS - set(state))
    if missing:
        raise CheckpointError(
            f"{source}: missing required keys {missing} — not an "
            "Executor checkpoint (or a torn/stale file)")
    if not isinstance(state["params"], dict):
        raise CheckpointError(
            f"{source}: 'params' is {type(state['params']).__name__}, "
            "expected a name->array dict")
    fmt = state.get("format")
    if fmt is not None:
        if not isinstance(fmt, dict):
            raise CheckpointError(
                f"{source}: 'format' is {type(fmt).__name__}, expected a "
                "dict tag")
        version = fmt.get("version")
        if version is not None and version not in SUPPORTED_FORMAT_VERSIONS:
            raise CheckpointError(
                f"{source}: format version {version} is newer than this "
                f"build supports ({SUPPORTED_FORMAT_VERSIONS}); upgrade "
                "hetu_tpu or re-save the checkpoint from the old version")
    return state


def atomic_write_bytes(blob, path):
    """Write ``blob`` to ``path`` via a same-directory temp file +
    ``os.replace``: a kill mid-write leaves the previous file intact and
    never a half-written one under the final name."""
    path = str(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return path


def atomic_pickle(state, path):
    """Pickle ``state`` to ``path`` torn-proof (tmp + ``os.replace``)."""
    return atomic_write_bytes(
        pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL), path)


def read_checkpoint(path):
    """Read + unpickle + validate a single-file checkpoint.

    Garbage, truncated, or non-checkpoint pickles surface as
    :class:`CheckpointError` with the path named; a missing file stays a
    ``FileNotFoundError`` (a different operator mistake)."""
    with open(path, "rb") as f:
        blob = f.read()
    try:
        state = pickle.loads(blob)
    except Exception as e:  # pickle raises a zoo of types on garbage
        raise CheckpointError(
            f"{path}: not a readable checkpoint "
            f"({type(e).__name__}: {e}) — torn write or corrupt file?"
        ) from e
    return validate_state(state, source=str(path))


def _state_tree(executor):
    return {
        "params": dict(executor.params),
        "opt_state": executor.opt_state,
        "meta": {
            "global_step": jnp.asarray(executor._global_step),
            "base_key": jax.random.key_data(executor._base_key),
        },
    }


def _abstract(leaf):
    """Restore template leaf: shape/dtype + the LIVE sharding so orbax
    reassembles each host's shards in place (no full-host materialization)."""
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=leaf.sharding)
    arr = jnp.asarray(leaf)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def save_sharded(executor, path):
    """Write a sharded (orbax) checkpoint of params + optimizer state +
    RNG.  Safe to call from every process of a multi-host run."""
    import orbax.checkpoint as ocp
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(str(path), _state_tree(executor), force=True)
    ckptr.wait_until_finished()


def restore_sharded_state(executor, path):
    """Read a sharded (orbax) checkpoint back into a
    ``Executor.state_dict``-shaped payload WITHOUT mutating the
    executor — so callers (the rolling checkpoint manager) can validate
    the restored state and still fall back to an older checkpoint with
    the live executor untouched."""
    import orbax.checkpoint as ocp
    ckptr = ocp.StandardCheckpointer()
    template = jax.tree_util.tree_map(_abstract, _state_tree(executor))
    state = ckptr.restore(str(path), template)
    return {
        "params": state["params"],
        "opt_state": state["opt_state"],
        "global_step": int(state["meta"]["global_step"]),
        "base_key": state["meta"]["base_key"],
    }


def load_sharded(executor, path):
    """Restore a sharded checkpoint into the executor, preserving each
    value's current device placement/sharding."""
    # reuse the single restore contract (Executor.load_state_dict)
    executor.load_state_dict(restore_sharded_state(executor, path))
    return executor


# -- cross-geometry restore (elastic training) -----------------------------

def executor_geometry(executor):
    """JSON-able description of the geometry an executor's state lives
    under: mesh axis sizes, device count, and per-param partition
    specs.  Recorded in the rolling-checkpoint manifest at save time so
    a restore into a DIFFERENT geometry is a validated decision
    (:func:`restore_resharded`), never an orbax shape error halfway
    through a restore."""
    mesh = getattr(executor, "mesh", None)
    geom = {
        "mesh": ({k: int(v) for k, v in mesh.shape.items()}
                 if mesh is not None else None),
        "devices": int(mesh.devices.size) if mesh is not None else 1,
        "params": {},
    }
    for name, v in executor.params.items():
        spec = getattr(getattr(v, "sharding", None), "spec", None)
        geom["params"][name] = str(spec) if spec is not None else None
    return geom


def geometry_compatible(saved, live):
    """True when a checkpoint written under ``saved`` restores into
    ``live`` without resharding (same mesh axis sizes, device count,
    and param partition specs).  Missing evidence (legacy manifest
    entry) counts as compatible — the old behavior."""
    if not saved or not live:
        return True
    return (saved.get("mesh") == live.get("mesh")
            and saved.get("devices") == live.get("devices")
            and saved.get("params") == live.get("params"))


def describe_geometry(geom):
    """One-line human form of an :func:`executor_geometry` dict."""
    if not geom:
        return "<unknown geometry>"
    mesh = geom.get("mesh")
    axes = ("x".join(f"{k}={v}" for k, v in mesh.items())
            if mesh else "unmeshed")
    return f"mesh[{axes}] over {geom.get('devices', '?')} device(s)"


_SLOT_RE = re.compile(r"(?:^|/)slots/([^/]+)(?:/|$)")


def state_shardings(executor):
    """Target-sharding lookup for :func:`restore_resharded`, derived
    from a LIVE executor built under the TARGET geometry: a callable
    ``keypath -> Sharding | None`` over ``/``-joined state-tree paths.
    Params resolve by name, optimizer slots follow their parameter
    (the slot name is in the path, so the writer's optimizer-op naming
    doesn't matter), meta leaves stay unsharded (host)."""
    by_param = {}
    for name, v in executor.params.items():
        sh = getattr(v, "sharding", None)
        if sh is not None:
            by_param[name] = sh

    def lookup(keypath):
        parts = keypath.split("/")
        if parts[0] == "params" and len(parts) == 2:
            return by_param.get(parts[1])
        if parts[0] == "opt_state":
            m = _SLOT_RE.search(keypath)
            if m:
                return by_param.get(m.group(1))
        return None

    return lookup


def restore_resharded(path, target_shardings):
    """Restore an orbax checkpoint written under ANY source geometry
    into TARGET shardings — the elastic-training restore: the writer's
    mesh may be gone (a chip died), the reader's mesh is whatever
    survived.

    ``target_shardings``: a callable ``keypath -> Sharding | None``
    (see :func:`state_shardings`) or a dict keyed by ``/``-joined
    state-tree paths; ``None`` leaves a leaf on the host (replicated).

    Primary path: abstract-template restore — the template substitutes
    the TARGET ``NamedSharding`` per leaf (shape/dtype come from the
    checkpoint's own metadata, so no source executor is needed) and
    orbax reads each array straight into its target layout.  Fallback
    (an orbax build that refuses a cross-topology template): restore to
    host arrays, then ``jax.device_put`` per leaf — the host-gather
    path, always correct on CPU, just not zero-copy.

    Returns an ``Executor.state_dict``-shaped payload; a target
    sharding that cannot tile a leaf's shape falls back to replicated
    for that leaf (optimizer scalars riding a sharded param's slot
    dict)."""
    import orbax.checkpoint as ocp
    from jax.tree_util import tree_map_with_path

    ckptr = ocp.StandardCheckpointer()
    try:
        # StepMetadata.item_metadata.tree: the saved state as a plain
        # nested dict whose leaves carry .shape and .dtype
        meta = ckptr.metadata(str(path)).item_metadata.tree
    except Exception as e:
        raise CheckpointError(
            f"{path}: unreadable checkpoint metadata "
            f"({type(e).__name__}: {e})") from e
    if callable(target_shardings):
        lookup = target_shardings
    else:
        spec_map = dict(target_shardings or {})
        lookup = spec_map.get

    def _keystr(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)

    def _target(kp, shape):
        sh = lookup(_keystr(kp))
        if sh is not None:
            try:
                sh.shard_shape(tuple(shape))
            except Exception:
                sh = None       # spec can't tile this leaf: replicate
        return sh

    def _template(kp, m, with_shardings):
        shape, dtype = tuple(m.shape), m.dtype
        sh = _target(kp, shape) if with_shardings else None
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    try:
        tmpl = tree_map_with_path(
            lambda kp, m: _template(kp, m, True), meta)
        state = ckptr.restore(str(path), tmpl)
    except Exception as e:
        # host-gather fallback: read every leaf replicated, then place
        logging.getLogger(__name__).warning(
            "%s: restore into the target shardings failed (%s: %s); "
            "reading every leaf to the host and placing it from there",
            path, type(e).__name__, e)
        tmpl = tree_map_with_path(
            lambda kp, m: _template(kp, m, False), meta)
        try:
            state = ckptr.restore(str(path), tmpl)
        except Exception as e:
            raise CheckpointError(
                f"{path}: unrestorable shard set "
                f"({type(e).__name__}: {e})") from e

        def _place(kp, v):
            sh = _target(kp, np.shape(v))
            return jax.device_put(np.asarray(v), sh) if sh is not None \
                else v
        state = tree_map_with_path(_place, state)
    return {
        "params": state["params"],
        "opt_state": state["opt_state"],
        "global_step": int(state["meta"]["global_step"]),
        "base_key": state["meta"]["base_key"],
    }
