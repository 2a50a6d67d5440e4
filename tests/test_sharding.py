"""Logical sharding rules: mapping, divisibility fallback, duplicates."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_local_mesh
from repro.models.params import p, tree_abstract, tree_init
from repro.sharding import DEFAULT_RULES, apply_rules, shardings_for
from repro.sharding.context import constrain, sharding_ctx


def test_apply_rules_local_mesh_all_replicated_when_indivisible():
    mesh = make_local_mesh()
    spec = apply_rules(("embed", "heads"), (7, 13), mesh)
    # axes of size 1 divide everything; spec may name them — sizes are 1
    for s in spec:
        if s is not None:
            assert all(mesh.shape[a] == 1 for a in
                       ((s,) if isinstance(s, str) else s))


def test_divisibility_fallback():
    import numpy as np
    devs = np.array(jax.devices() * 1)  # 1 device
    mesh = make_local_mesh()
    # dim 6 % 4 != 0 on a 4-wide axis → dropped; emulate via fake shape calc
    spec = apply_rules(("kv_heads",), (6,), mesh)
    assert isinstance(spec, P)


def test_duplicate_axis_not_reused():
    mesh = make_local_mesh()
    spec = apply_rules(("heads", "act_heads"), (4, 4), mesh)
    named = [s for s in spec if s is not None]
    flat = []
    for s in named:
        flat.extend((s,) if isinstance(s, str) else s)
    assert len(flat) == len(set(flat))


def test_shardings_for_paramspec_tree():
    mesh = make_local_mesh()
    specs = {"w": p((8, 16), ("embed", "ffn")),
             "b": p((16,), ("ffn",), init="zeros")}
    sh = shardings_for(specs, mesh)
    assert sh["w"].mesh == mesh


def test_constrain_noop_outside_ctx():
    x = jax.numpy.ones((4, 4))
    y = constrain(x, ("batch", "act_embed"))
    assert (y == x).all()


def test_constrain_inside_ctx():
    mesh = make_local_mesh()
    x = jax.numpy.ones((4, 4))
    with sharding_ctx(mesh, None):
        y = constrain(x, ("batch", "act_embed"))
    assert (y == x).all()


def test_tree_init_matches_abstract():
    specs = {"w": p((4, 6), ("embed", "ffn")),
             "n": p((6,), ("norm",), init="ones")}
    ab = tree_abstract(specs)
    real = tree_init(specs, jax.random.PRNGKey(0))
    assert ab["w"].shape == real["w"].shape
    assert ab["w"].dtype == real["w"].dtype
    assert float(real["n"].sum()) == 6.0
