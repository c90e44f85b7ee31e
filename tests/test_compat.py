"""Unit tests for `repro.compat` — adapters over the installed jax (0.9.0).

These exist so a jax upgrade fails HERE, loudly and attributably, instead
of deep inside the model, pipeline or perf-invariant code: the Auto-axis
mesh helper, the jaxpr walkers the perf-invariant tests build on, and jit
cache introspection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import compat
from repro.launch.mesh import make_local_mesh


# ---------------------------------------------------------------------------
# make_mesh: Auto axes (jax.make_mesh defaults to Explicit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("names", [("pod",), ("data", "model"),
                                   ("pod", "data", "model")])
def test_make_mesh_axes_are_auto(names):
    mesh = compat.make_mesh((1,) * len(names), names)
    assert mesh.axis_names == names
    assert mesh.axis_types == (AxisType.Auto,) * len(names)
    assert mesh.devices.size == 1


def test_make_local_mesh_is_auto():
    mesh = make_local_mesh(model=1, data=1)
    assert mesh.axis_names == ("data", "model")
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_make_mesh_grad_under_mesh_context():
    """A sharding-constrained gather + grad under `with mesh:` — the
    pattern that an Explicit-axis mesh refuses (embedding lookup of a
    sharded table, differentiated)."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    emb = jnp.arange(12.0).reshape(6, 2)
    tok = jnp.array([[0, 5], [2, 2]])

    def loss(e):
        e = jax.lax.with_sharding_constraint(
            e, NamedSharding(mesh, P("model", None)))
        return jnp.sum(e[tok] ** 2)

    with mesh:
        g = jax.jit(jax.grad(loss))(emb)
    want = np.zeros((6, 2))
    for t in np.asarray(tok).ravel():
        want[t] += 2 * np.asarray(emb)[t]
    np.testing.assert_array_equal(np.asarray(g), want)


# ---------------------------------------------------------------------------
# jaxpr walkers (what test_perf_invariants / test_stacked_vmap build on)
# ---------------------------------------------------------------------------

def _cond_sort_fn(x):
    y = jnp.sort(x)                                  # unconditional sort
    return jax.lax.cond(y[0] > 0.0,
                        lambda v: jnp.sort(-v),      # sort inside cond
                        lambda v: v, y)


def test_walk_primitives_distinguishes_cond_branches():
    jx = jax.make_jaxpr(_cond_sort_fn)(jnp.arange(4.0))
    prims = list(compat.walk_primitives(jx.jaxpr))
    assert ("sort", False) in prims, "missed the unconditional sort"
    assert ("sort", True) in prims, "missed the cond-gated sort"
    # nesting flag is sticky: everything under the cond is flagged
    assert all(in_cond for p, in_cond in prims if p == "sort" and in_cond)


def test_walk_primitives_descends_into_scan_bodies():
    def scanned(x):
        return jax.lax.scan(lambda c, _: (jnp.sort(c), None), x,
                            jnp.arange(3))[0]
    jx = jax.make_jaxpr(scanned)(jnp.arange(4.0))
    assert ("sort", False) in compat.walk_primitives(jx.jaxpr)


def test_sub_jaxprs_unwraps_closed_lists_and_ignores_scalars():
    jx = jax.make_jaxpr(_cond_sort_fn)(jnp.arange(4.0))
    cond_eqn = next(e for e in jx.jaxpr.eqns if e.primitive.name == "cond")
    branches = cond_eqn.params["branches"]
    subs = compat.sub_jaxprs(branches)
    assert len(subs) == 2 and all(isinstance(j, compat.Jaxpr) for j in subs)
    assert compat.sub_jaxprs(jx) == [jx.jaxpr]   # ClosedJaxpr unwraps
    assert compat.sub_jaxprs(3) == []
    assert compat.sub_jaxprs([jx.jaxpr, (branches[0],)]) \
        == [jx.jaxpr, branches[0].jaxpr]


# ---------------------------------------------------------------------------
# jit cache introspection (bench-smoke's one-XLA-program gate)
# ---------------------------------------------------------------------------

def test_jit_cache_size_counts_distinct_programs():
    @jax.jit
    def g(x):
        return x * 2

    base = compat.jit_cache_size(g)
    g(jnp.zeros((2,)))
    g(jnp.zeros((3,)))                           # new shape -> new program
    g(jnp.zeros((3,)))                           # cache hit -> no new program
    assert compat.jit_cache_size(g) - base == 2
