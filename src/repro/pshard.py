"""Mesh-aware sharding helpers.

Models annotate activations with *logical* axis specs; ``constrain`` resolves
them against the currently-installed mesh, dropping axes the mesh doesn't have
(so the same model code runs on a single CPU device, a (data, model) pod, or a
(pod, data, model) multi-pod mesh).
"""
from __future__ import annotations

import contextlib
import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None
_MANUAL: tuple = ()  # axes currently inside a shard_map manual region

# Logical batch axis: models constrain batch dims with the BATCH sentinel;
# 'tp' sharding resolves it to ('pod','data'), 'fsdp' to
# ('pod','data','model') (pure ZeRO-3: both axes act data-parallel).
BATCH = "__batch__"
_BATCH_AXES: tuple = ("pod", "data")


def set_batch_axes(axes) -> None:
    global _BATCH_AXES
    _BATCH_AXES = tuple(axes)


def get_batch_axes() -> tuple:
    return _BATCH_AXES


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


@contextlib.contextmanager
def manual_axes(axes):
    """Mark mesh axes as shard_map-manual. The pod regions are manual over
    every axis, so inside one ``constrain`` and ``param_shardings`` are
    no-ops."""
    global _MANUAL
    prev, _MANUAL = _MANUAL, tuple(axes)
    try:
        yield
    finally:
        _MANUAL = prev


def get_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    global _MESH
    prev, _MESH = _MESH, mesh
    try:
        yield
    finally:
        _MESH = prev


def _filter_axis(axis, names):
    if axis is None:
        return None
    if axis == BATCH:
        axis = _BATCH_AXES
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in names)
        return kept if len(kept) > 1 else (kept[0] if kept else None)
    return axis if axis in names else None


def resolve_spec(*spec) -> P:
    """Drop spec axes that the installed mesh doesn't provide (or that are
    currently shard_map-manual). A mesh axis may appear once: the first
    occurrence wins (e.g. fsdp batch = ('data','model') nulls a later
    'model' head constraint)."""
    names = _MESH.axis_names if _MESH is not None else ()
    names = tuple(n for n in names if n not in _MANUAL)
    used: set = set()
    out = []
    for a in spec:
        f = _filter_axis(a, names)
        if f is None:
            out.append(None)
            continue
        fs = f if isinstance(f, tuple) else (f,)
        kept = tuple(x for x in fs if x not in used)
        used.update(kept)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def _axis_size(ax) -> int:
    if ax is None or _MESH is None:
        return 1
    axes = ax if isinstance(ax, (tuple, list)) else (ax,)
    sizes = dict(_MESH.shape)  # works for Mesh and AbstractMesh
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def size_filter(spec: P, shape) -> P:
    """Drop spec axes whose mesh size doesn't divide the dim (jit
    in_shardings require exact divisibility; e.g. 8 or 36 heads vs model=16)."""
    out = []
    for i, ax in enumerate(spec):
        if i >= len(shape) or ax is None:
            out.append(ax if i < len(shape) else None)
            continue
        n = _axis_size(ax)
        out.append(ax if (n > 0 and shape[i] % n == 0 and shape[i] >= n) else None)
    return P(*out)


def constrain(x, *spec):
    """with_sharding_constraint against the installed mesh (no-op if none,
    and inside a shard_map region, where every mesh axis is manual)."""
    if _MESH is None or len(_MESH.axis_names) == 0 or _MANUAL:
        return x
    resolved = size_filter(resolve_spec(*spec), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, resolved))


def named_sharding(*spec) -> Optional[NamedSharding]:
    if _MESH is None:
        return None
    return NamedSharding(_MESH, resolve_spec(*spec))


# --------------------------------------------------------------------------- #
# Rule-based parameter sharding
# --------------------------------------------------------------------------- #

def spec_for_param(path: str, shape, rules) -> P:
    """First regex rule matching ``path`` wins; rules map pattern -> spec
    tuple. Axes that don't divide the dim are dropped (size_filter)."""
    for pat, spec in rules:
        if re.search(pat, path):
            cleaned = []
            for i, ax in enumerate(spec):
                if ax is None or i >= len(shape):
                    cleaned.append(None)
                    continue
                cleaned.append(ax)
            return size_filter(resolve_spec(*cleaned[: len(shape)]), shape)
    return resolve_spec(*([None] * len(shape)))


def tree_path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(params, rules):
    """Pytree of NamedSharding for a param pytree, by path-regex rules."""
    def one(path, leaf):
        if _MESH is None or _MANUAL:
            return None
        spec = spec_for_param(tree_path_str(path), leaf.shape, rules)
        return NamedSharding(_MESH, spec)
    return jax.tree_util.tree_map_with_path(one, params)


def param_specs(params, rules):
    """Pytree of PartitionSpec (mesh-filtered) for a param pytree."""
    def one(path, leaf):
        return spec_for_param(tree_path_str(path), leaf.shape, rules)
    return jax.tree_util.tree_map_with_path(one, params)
