"""The engine loop's node-major tiles against the slot-major formulas.

Inside ``sim_jax._run_loop`` every slots x nodes array is held as
(nodes, slots), with the slots on the last axis (DESIGN.md §7). Each
helper that reads such a tile must give, bit for bit, what the
slot-major formula gives on the transposed inputs: the same compares,
integer sums and first-index argmax.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sim_jax

EPS = sim_jax._EPS


def _instance(seed, N, M=12):
    """Integer demands and free vectors with many exact fits and ties,
    single-node and gang assignments, and all-False rows."""
    rng = np.random.default_rng(seed)
    demand = np.stack([rng.integers(1, 9, N), rng.integers(1, 33, N),
                       rng.choice([0, 1, 2, 4, 8], N)], 1).astype(np.float32)
    free = np.stack([rng.integers(0, 9, M), rng.integers(0, 33, M),
                     rng.choice([0, 1, 2, 4, 8], M)], 1).astype(np.float32)
    free[M // 2:] = free[:M - M // 2]          # duplicate nodes: ties
    width = rng.choice([1, 1, 2, 3], N).astype(np.int32)
    assign = np.zeros((N, M), bool)
    for j in range(N):
        if rng.random() < 0.8:                 # the rest hold no node
            assign[j, rng.choice(M, width[j], replace=False)] = True
    mask = rng.random(N) < 0.5
    return (jnp.asarray(demand), jnp.asarray(free), jnp.asarray(width),
            jnp.asarray(assign), jnp.asarray(mask))


def _fits_slot_major(free, demand):
    return jnp.all(free[None, :, :] >= demand[:, None, :] - EPS, axis=2)


@pytest.mark.parametrize("seed,N", [(0, 37), (1, 300), (2, 1024)])
def test_node_major_helpers_match_slot_major(seed, N):
    demand, free, width, assign, mask = _instance(seed, N)
    demand_t, assign_t = demand.T, assign.T
    fits = _fits_slot_major(free, demand)

    # fit counts
    np.testing.assert_array_equal(
        sim_jax._fit_counts(free, demand_t - EPS),
        jnp.sum(fits, axis=1).astype(jnp.int32))

    # Eq. 2 best-victim slack and node, for several TE demands
    for te_d in (demand[0], demand[N // 2], jnp.zeros(3, jnp.float32)):
        slack = jnp.min(free[None, :, :] + demand[:, None, :]
                        - te_d[None, None, :], axis=2)
        slack = jnp.where(assign, slack, -jnp.inf)
        best, node = sim_jax._best_victim_node(free, assign_t, demand_t,
                                               te_d)
        np.testing.assert_array_equal(best, jnp.max(slack, axis=1))
        np.testing.assert_array_equal(node, jnp.argmax(slack, axis=1))

    # gang release: the summed demand of the masked jobs per node
    sel = (assign & mask[:, None]).astype(jnp.float32)
    np.testing.assert_array_equal(
        sim_jax._gang_release(assign_t, demand_t, mask), sel.T @ demand)

    # first-fit mask of each slot, from its own demand column
    def first_fit(j):
        _, nodes = sim_jax._gang_fit(free, sim_jax._slot_col(demand_t, j),
                                     width[j])
        return nodes

    row_ok = jnp.sum(fits, axis=1) >= width
    expect = (fits & (jnp.cumsum(fits, axis=1) <= width[:, None])
              & row_ok[:, None])
    np.testing.assert_array_equal(jax.vmap(first_fit)(jnp.arange(N)), expect)

    # one slot's column, read and written through its lane block
    for j in {0, 1, N // 2, N - 2, N - 1}:
        np.testing.assert_array_equal(sim_jax._slot_col(assign_t, j),
                                      assign[j])
        np.testing.assert_array_equal(sim_jax._slot_col(demand_t, j),
                                      demand[j])
        col = ~assign[j]
        np.testing.assert_array_equal(
            sim_jax._set_slot_col(assign_t, j, col),
            assign.at[j].set(col).T)
