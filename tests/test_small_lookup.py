"""`engine.small_lookup` equals plain indexing, bit for bit, and builds no
gather: bool, int32 and f32 tables (-0.0 and NaN payloads included), 4, 6,
8 and 17 entries, indices of shape (E,) and (C, E), a table shared across
the leading axes, and under `jax.vmap` over two leading axes. So does
`sms._at`, the SMS stages' read along one small axis of a FIFO or DCS
table: that axis first, in the middle or last, with an index broadcast
over the table's other axes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import engine, sms

C, E, P, R = 4, 134, 3, 2
# quiet NaN with a payload; the select chain must carry its bits through
NAN_PAYLOAD = np.array([0x7FC0_1234], np.uint32).view(np.float32)[0]


def _table(rng, dtype, shape):
    if dtype == "bool":
        return rng.random(shape) < 0.5
    if dtype == "int32":
        return rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)
    t = rng.standard_normal(shape).astype(np.float32)
    t[..., 0], t[..., 1], t[..., -1] = -0.0, NAN_PAYLOAD, np.inf
    return t


def _index(rng, k, shape):
    idx = rng.integers(0, k, shape, dtype=np.int32)
    idx[..., :k] = np.arange(k)             # every entry of the table read
    return idx


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


CASES = {
    # index shape -> (table shape, index shape, lookup, reference)
    "E": ((), (E,), engine.small_lookup, lambda t, i: t[i]),
    "CE": ((C,), (C, E), engine.small_lookup,
           lambda t, i: np.take_along_axis(t, i, -1)),
    "shared": ((), (C, E), engine.small_lookup, lambda t, i: t[i]),
    "vmap2": ((P, R, C), (P, R, C, E),
              jax.vmap(jax.vmap(engine.small_lookup)),
              lambda t, i: np.take_along_axis(t, i, -1)),
    # sms._at drops the small axis: out[...] = table[..., idx[...]] with
    # the table's entries moved to the axis `_at` is told to read
    "at_axis0": ((E,), (E,),
                 lambda t, i: sms._at(jnp.moveaxis(t, -1, 0), i, axis=0),
                 lambda t, i: np.take_along_axis(t, i[:, None], -1)[:, 0]),
    "at_axis1": ((C, E), (C, E),
                 lambda t, i: sms._at(jnp.moveaxis(t, -1, 1), i, axis=1),
                 lambda t, i: np.take_along_axis(t, i[..., None], -1)[..., 0]),
    "at_shared": ((C, E), (E,), sms._at,
                  lambda t, i: np.take_along_axis(
                      t, np.broadcast_to(i, (C, E))[..., None], -1)[..., 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("k", [4, 6, 8, 17])
@pytest.mark.parametrize("dtype", ["bool", "int32", "float32"])
def test_small_lookup_is_exact_indexing(dtype, k, case):
    lead, ishape, fn, ref = CASES[case]
    rng = np.random.default_rng(k * 1000 + len(ishape))
    table, idx = _table(rng, dtype, lead + (k,)), _index(rng, k, ishape)
    out = jax.jit(fn)(table, idx)
    want = ref(table, idx)
    assert out.dtype == table.dtype and out.shape == want.shape
    np.testing.assert_array_equal(_bits(out), _bits(want))
    prims = {p for p, _ in compat.walk_primitives(
        jax.make_jaxpr(fn)(table, idx).jaxpr)}
    assert "gather" not in prims and "select_n" in prims
