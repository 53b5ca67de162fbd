"""`tm_tpu._presyn_active_packed` alone: the packed-column membership test
("is this synapse's presynaptic cell in the active set?") against a flat
`np.isin` over cell ids. The function is a select chain over the Ac packed
columns with a shift/mask decode where K is a power of two (ISSUE 36); every
step that uses it is held to the oracle elsewhere (test_tm_parity.py,
test_tm_forms.py) — this file holds the function's own edges: empty slots
(-1), fill column ids (C), an empty active set, a full Ac, both cell-id
dtypes, and a K that is no power of two (which keeps `//` and `%`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu

C, AC = 96, 10  # C * 32 = 3,072 cell ids: inside int16


def _active_set(rng, K: int, scenario: str):
    """-> (cells [C, K] bool, col_ids [AC] ascending with C fills, col_masks
    [AC]). Fill entries carry an all-ones mask here, which `_pack_active`
    never makes (it gives 0): a fill must not match by its id alone."""
    n_cols = {"sparse": 4, "empty_set": 0, "full_ac": AC, "no_synapses": 4}[scenario]
    cols = np.sort(rng.choice(C, size=n_cols, replace=False))
    cells = np.zeros((C, K), bool)
    for c in cols:
        cells[c, rng.choice(K, size=rng.integers(1, K + 1), replace=False)] = True
    if scenario == "full_ac":  # the edges of both ranges, and a full mask
        cells[cols[0], 0] = cells[cols[-1], K - 1] = True
        cells[cols[3]] = True
    col_ids = np.full(AC, C, np.int32)
    col_ids[:n_cols] = cols
    col_masks = np.full(AC, -1, np.int32)
    col_masks[:n_cols] = (cells[cols].astype(np.int64) << np.arange(K)).sum(-1).astype(np.uint32).view(np.int32)
    return cells, col_ids, col_masks


def _presyn(rng, K: int, shape, dtype, scenario: str) -> np.ndarray:
    if scenario == "no_synapses":
        return np.full(shape, -1, dtype)
    p = rng.integers(0, C * K, size=shape)
    p[rng.random(shape) < 0.3] = -1  # empty slots
    flat = p.reshape(-1)
    flat[:4] = (0, C * K - 1, K - 1, K)  # the id range's ends, a column's edge
    return p.astype(dtype)


@pytest.mark.parametrize("scenario", ["sparse", "empty_set", "full_ac", "no_synapses"])
@pytest.mark.parametrize("dtype", [np.int16, np.int32], ids=["i16", "i32"])
@pytest.mark.parametrize("K", [8, 32, 6])
def test_presyn_active_packed_equals_flat_isin(K, dtype, scenario):
    rng = np.random.Generator(np.random.Philox(key=(36, K)))
    cells, col_ids, col_masks = _active_set(rng, K, scenario)
    active_ids = np.flatnonzero(cells.reshape(-1))
    # the learning rows [L, M], a flat pool [C, K*S*M], a wide one [C, K, S, M]
    for shape in ((48, 16), (C, K * 2 * 4), (C, K, 2, 4)):
        presyn = _presyn(rng, K, shape, dtype, scenario)
        want = np.isin(presyn, active_ids)
        got = tm_tpu._presyn_active_packed(
            jnp.asarray(presyn), jnp.asarray(col_ids), jnp.asarray(col_masks), K)
        assert got.dtype == jnp.bool_ and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=f"shape={shape}")
        # the case is not vacuous: something is a member wherever it can be
        assert want.any() == (scenario in ("sparse", "full_ac"))

    # the set as the step makes it (`_pack_active`), batched as the step runs it
    pk_ids, pk_masks, n = tm_tpu._pack_active(jnp.asarray(cells), AC)
    assert int(n) == cells.any(-1).sum()
    np.testing.assert_array_equal(np.asarray(pk_ids), col_ids)
    presyn_g = np.stack([_presyn(rng, K, (C, K * 8), dtype, scenario) for _ in range(3)])
    got_g = jax.jit(jax.vmap(tm_tpu._presyn_active_packed, in_axes=(0, None, None, None)),
                    static_argnums=3)(jnp.asarray(presyn_g), pk_ids, pk_masks, K)
    np.testing.assert_array_equal(np.asarray(got_g), np.isin(presyn_g, active_ids))
