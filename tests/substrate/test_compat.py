"""Targeted tests for ``repro.compat`` — the thin helpers over the
installed JAX's mesh, shard_map and cost-analysis APIs.

Each helper gets its own test so that the day a new JAX release moves an
API again, CI reports a named compat failure instead of collateral
damage across the whole suite."""
import inspect

import jax
import jax.numpy as jnp
import pytest

from repro import compat


# ------------------------------------------------------------- make_mesh
def test_make_mesh_real_call():
    mesh = compat.make_mesh((1,), ("x",))
    assert mesh.axis_names == ("x",)
    assert mesh.devices.size == 1


def test_make_mesh_passes_auto_axis_types_when_supported(monkeypatch):
    calls = {}

    def fake_make_mesh(shape, axes, **kwargs):
        calls.update(kwargs)
        return "mesh"

    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    assert compat.make_mesh((1, 1), ("a", "b")) == "mesh"
    assert calls["axis_types"] == (jax.sharding.AxisType.Auto,) * 2


def test_make_mesh_caller_override_wins(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax, "make_mesh",
                        lambda shape, axes, **kw: calls.update(kw) or "m")
    explicit = (jax.sharding.AxisType.Explicit,)
    compat.make_mesh((1,), ("x",), axis_types=explicit)
    assert calls["axis_types"] == explicit


# ------------------------------------------------------------- shard_map
def test_shard_map_import_resolved():
    """The installed JAX exports ``shard_map`` at top level with the
    ``check_vma`` spelling the helper passes."""
    assert callable(jax.shard_map)
    assert "check_vma" in inspect.signature(jax.shard_map).parameters


def test_shard_map_behavioral():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("x",))
    f = compat.shard_map(lambda a: a * 2, mesh=mesh, in_specs=P("x"),
                         out_specs=P("x"), check_vma=False)
    out = f(jnp.arange(4, dtype=jnp.float32))
    assert out.tolist() == [0.0, 2.0, 4.0, 6.0]


@pytest.mark.parametrize("params,expected_kwarg", [
    (frozenset({"f", "mesh", "in_specs", "out_specs", "check_vma"}),
     "check_vma"),
])
def test_shard_map_flag_renamed_per_signature(monkeypatch, params,
                                              expected_kwarg):
    """``check_vma`` reaches ``jax.shard_map`` under that name."""
    seen = {}

    def fake_impl(f, mesh=None, in_specs=None, out_specs=None, **kwargs):
        seen.update(kwargs)
        return "wrapped"

    assert expected_kwarg in params
    monkeypatch.setattr(jax, "shard_map", fake_impl)
    assert compat.shard_map(lambda x: x, mesh="m", in_specs=(),
                            out_specs=(), check_vma=False) == "wrapped"
    assert seen == {expected_kwarg: False}


# --------------------------------------------------------- cost_analysis
class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        return self._ca


def test_cost_analysis_list_vs_dict():
    flat = {"flops": 8.0, "bytes accessed": 64.0}
    assert compat.cost_analysis(_FakeCompiled(flat)) == flat


def test_cost_analysis_real_compiled():
    compiled = jax.jit(lambda x: x * 2 + 1).lower(
        jnp.arange(8, dtype=jnp.float32)).compile()
    ca = compat.cost_analysis(compiled)
    assert isinstance(ca, dict)       # flat, never a list
    assert ca["flops"] > 0
