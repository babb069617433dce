"""Direct coverage of repro.compat — the jax API shim layer.

Every other test exercises compat incidentally (via the evaluator or the
kernels); these pin the shim's own contract so a jax upgrade that silently
changes a symbol fails here with a named test rather than deep inside a
shard-mapped trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


def test_all_exports_exist():
    for name in compat.__all__:
        assert callable(getattr(compat, name)), name


def test_default_search_devices_nonempty():
    devs = compat.default_search_devices()
    assert devs and devs == list(jax.local_devices())


def test_make_mesh_shapes():
    mesh = compat.make_mesh()
    assert mesh.axis_names == ("search",)
    assert mesh.devices.size == len(jax.local_devices())
    one = compat.make_mesh(jax.local_devices()[:1], axis="x")
    assert one.axis_names == ("x",)
    assert one.devices.size == 1


def test_shard_map_runs_and_matches_unsharded():
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh()
    n = mesh.devices.size

    def body(x):
        return x * 2.0 + 1.0

    f = compat.shard_map(
        body, mesh=mesh, in_specs=P("search"), out_specs=P("search"))
    x = jnp.arange(4 * n, dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 2.0 + 1.0)


def test_shard_map_check_vma_flag_accepted():
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh()
    n = mesh.devices.size

    def body(x):
        return x + 1.0

    f = compat.shard_map(
        body, mesh=mesh, in_specs=P("search"), out_specs=P("search"),
        check_vma=False)
    x = jnp.ones((2 * n,), dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) + 1.0)


def test_pallas_tpu_compiler_params_fields():
    params = compat.pallas_tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
    from jax.experimental.pallas import tpu as pltpu

    assert isinstance(params, pltpu.CompilerParams)


def test_pallas_tpu_compiler_params_rejects_unknown_field():
    with pytest.raises(TypeError):
        compat.pallas_tpu_compiler_params(not_a_real_field=1)
