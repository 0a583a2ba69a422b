"""The compat shim: the shard_map adapter over monkeypatched signatures,
plus the real resolution on the installed jax; the manual-mesh (axis-env)
helpers against a fake AxisEnv and against the real shard_map."""
import inspect
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


def _capture(calls):
    """A fake shard_map core that records how it was invoked."""

    def run(f, **kwargs):
        calls.append(kwargs)
        return f

    return run


def test_adapt_passes_check_vma_through_on_modern_jax():
    calls = []
    run = _capture(calls)

    def modern(f, *, mesh, in_specs, out_specs, check_vma=True):
        return run(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=check_vma)

    wrapped = compat.adapt_shard_map(modern)
    wrapped(lambda x: x, mesh="M", in_specs="i", out_specs="o", check_vma=False)
    assert calls[0]["check_vma"] is False


def test_adapt_drops_flag_when_impl_has_neither_kwarg():
    calls = []
    run = _capture(calls)

    def bare(f, *, mesh, in_specs, out_specs):
        return run(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)

    wrapped = compat.adapt_shard_map(bare)
    wrapped(lambda x: x, mesh="M", in_specs="i", out_specs="o", check_vma=False)
    assert "check_rep" not in calls[0] and "check_vma" not in calls[0]


def test_adapt_omits_flag_when_unset():
    calls = []
    run = _capture(calls)

    def modern(f, *, mesh, in_specs, out_specs, **kw):
        return run(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)

    wrapped = compat.adapt_shard_map(modern)
    wrapped(lambda x: x, mesh="M", in_specs="i", out_specs="o")
    assert "check_rep" not in calls[0] and "check_vma" not in calls[0]


def test_resolve_finds_installed_shard_map():
    impl = compat.resolve_shard_map()
    assert callable(impl)
    params = inspect.signature(impl).parameters
    assert "check_vma" in params
    assert compat._check_kwarg_name(impl) == "check_vma"


def test_shard_map_executes_on_installed_jax():
    """End-to-end: the shim actually runs a shard_map on a 1-device mesh."""
    from jax.sharding import PartitionSpec

    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.arange(8, dtype=jnp.float32)
    out = compat.shard_map(
        lambda v: v * 2.0,
        mesh=mesh,
        in_specs=PartitionSpec("d"),
        out_specs=PartitionSpec("d"),
        check_vma=False,
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 2.0)


# --------------------------------------------------------------------------
# manual-mesh (axis-env) helpers
# --------------------------------------------------------------------------

def _modern_core(axis_sizes):
    """A jax.core lookalike exposing get_axis_env."""
    env = SimpleNamespace(axis_sizes=axis_sizes, spmd_axis_names=set())
    return SimpleNamespace(get_axis_env=lambda: env)


def test_axis_sizes_pure_helpers_both_generations():
    modern = compat.axis_sizes_from_env(
        SimpleNamespace(axis_sizes={"data": 4, "model": 2}))
    assert modern == {"data": 4, "model": 2}
    # empty environments read as "not in a manual region"
    assert compat.axis_sizes_from_env(SimpleNamespace(axis_sizes={})) == {}
    assert compat.axis_sizes_from_env(SimpleNamespace()) == {}


def test_manual_helpers_through_monkeypatched_modern_core(monkeypatch):
    """axis_env_sizes() reads through jax.core when it exposes
    get_axis_env."""
    env = SimpleNamespace(axis_sizes={"model": 8}, spmd_axis_names=set())
    monkeypatch.setattr(jax.core, "get_axis_env", lambda: env,
                        raising=False)
    assert compat.axis_env_sizes() == {"model": 8}
    assert compat.in_shard_map()
    assert compat.manual_axis_size("model") == 8
    with pytest.raises(KeyError):
        compat.manual_axis_size("data")


def test_manual_axis_size_products():
    reader = compat.axis_env_reader_for(_modern_core({"data": 4, "model": 2}))
    # product semantics via the pure reader feeding a fake jax.core
    sizes = reader()
    assert sizes["data"] * sizes["model"] == 8


def test_axis_env_on_installed_jax_inside_and_outside_shard_map():
    """The real thing: outside any region the env is empty; inside a
    compat.shard_map body every mesh axis (even 1-sized) is bound, so
    in_shard_map() is True and sizes/products resolve."""
    from jax.sharding import PartitionSpec

    assert compat.axis_env_sizes() == {}
    assert not compat.in_shard_map()

    seen = []
    mesh = jax.make_mesh((1,), ("d",))

    def body(v):
        seen.append((compat.axis_env_sizes(), compat.in_shard_map(),
                     compat.manual_axis_size("d")))
        return v

    compat.shard_map(body, mesh=mesh, in_specs=PartitionSpec("d"),
                     out_specs=PartitionSpec("d"), check_vma=False)(
        jnp.arange(4, dtype=jnp.float32))
    assert seen == [({"d": 1}, True, 1)]
    # and the env unwinds cleanly after the region
    assert compat.axis_env_sizes() == {}
