"""Thin layer over the jax API surface this repo shard-maps through.

The repo targets jax 0.9, where ``shard_map`` lives at ``jax.shard_map``
and takes the replication-check flag as ``check_vma=``.  Everything
that shard-maps goes through :func:`shard_map` below; the adapter
forwards ``check_vma`` only to an implementation that accepts it, and
:func:`adapt_shard_map` is the pure, cache-free core so tests can feed
it monkeypatched implementations.

Manual-mesh helpers: :func:`axis_env_sizes` / :func:`in_shard_map` /
:func:`manual_axis_size` answer the locality question the backend
registry needs — a Pallas kernel is per-device, so it is only legal on a
multi-device process when the call site is already device-local (traced
inside a shard_map body).  :func:`axis_env_reader_for` is the pure,
cache-free core over a ``jax.core``-like surface exposing
``get_axis_env()``.
"""
from __future__ import annotations

import functools
import inspect
from typing import Callable, Dict, Optional

import jax

__all__ = [
    "shard_map",
    "adapt_shard_map",
    "resolve_shard_map",
    "axis_sizes_from_env",
    "axis_env_reader_for",
    "axis_env_sizes",
    "in_shard_map",
    "manual_axis_size",
]


def resolve_shard_map() -> Callable:
    """The installed jax's shard_map implementation."""
    return jax.shard_map


def _check_kwarg_name(impl: Callable) -> Optional[str]:
    """``"check_vma"`` when ``impl`` accepts the flag, else ``None``."""
    try:
        params = inspect.signature(impl).parameters
    except (TypeError, ValueError):  # builtins / C impls: assume modern
        return "check_vma"
    return "check_vma" if "check_vma" in params else None


def adapt_shard_map(impl: Callable) -> Callable:
    """Wrap a shard_map implementation behind one calling convention.

    The returned callable has signature
    ``(f, *, mesh, in_specs, out_specs, check_vma=None)`` and forwards the
    check flag when ``impl`` accepts it (dropping it otherwise).
    """
    kwarg = _check_kwarg_name(impl)

    def call(f, *, mesh, in_specs, out_specs, check_vma=None):
        kwargs = {"mesh": mesh, "in_specs": in_specs, "out_specs": out_specs}
        if check_vma is not None and kwarg is not None:
            kwargs[kwarg] = check_vma
        return impl(f, **kwargs)

    return call


@functools.lru_cache(maxsize=1)
def _cached_adapter() -> Callable:
    return adapt_shard_map(resolve_shard_map())


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map`` behind :func:`adapt_shard_map`."""
    return _cached_adapter()(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


# --------------------------------------------------------------------------
# manual-mesh awareness: the named-axis environment
# --------------------------------------------------------------------------

def axis_sizes_from_env(env) -> Dict[str, int]:
    """Pure: ``AxisEnv`` -> ``{axis_name: size}``.

    The object carries an ``axis_sizes`` mapping; anything without one
    (or ``None``) reads as "no axes bound".  Names that are not plain
    strings (the sentinel an unnamed vmap binds) do not count.
    """
    sizes = getattr(env, "axis_sizes", None)
    if not sizes:
        return {}
    return {name: int(size) for name, size in dict(sizes).items()
            if isinstance(name, str)}


def axis_env_reader_for(core) -> Callable[[], Dict[str, int]]:
    """Build an axis-size reader over a ``jax.core``-like surface.

    ``core`` exposes ``get_axis_env()`` (an ``AxisEnv`` with
    ``axis_sizes``); the returned zero-arg callable yields
    ``{axis_name: size}``.  A surface without it is never inside a
    manual region.
    """
    get_env = getattr(core, "get_axis_env", None)
    if get_env is None:
        return dict
    return lambda: axis_sizes_from_env(get_env())


def _installed_axis_env_reader() -> Callable[[], Dict[str, int]]:
    """The installed jax's axis environment (public ``jax.core`` first,
    then its private home).  Resolved per call — it happens at trace
    time, and late binding keeps monkeypatched ``jax.core`` surfaces in
    tests honest.
    """
    if getattr(jax.core, "get_axis_env", None) is not None:
        return axis_env_reader_for(jax.core)
    from jax._src import core as src_core

    return axis_env_reader_for(src_core)


def axis_env_sizes() -> Dict[str, int]:
    """Named mesh axes bound at the current trace point -> their sizes.

    Empty outside any manually-mapped region; inside a ``shard_map``
    body it maps every mesh axis name to its mesh size (a 1-sized axis
    still counts — the body is device-local either way).
    """
    return _installed_axis_env_reader()()


def in_shard_map() -> bool:
    """Whether the current trace point is inside a manually-mapped
    (device-local) region — a ``shard_map`` body (``pmap`` and
    axis-named ``vmap`` also register; the repo uses neither).
    """
    return bool(axis_env_sizes())


def manual_axis_size(*names: str) -> int:
    """Product of the named bound axes' sizes (the local shard count
    over those axes).  Unbound names raise — asking for an axis outside
    its shard_map is a bug, not a 1.
    """
    sizes = axis_env_sizes()
    total = 1
    for name in names:
        if name not in sizes:
            raise KeyError(
                f"axis {name!r} is not bound at this trace point; "
                f"bound axes: {sorted(sizes)}")
        total *= sizes[name]
    return total
