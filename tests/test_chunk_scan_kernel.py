"""The Mamba-2 chunked form as a Pallas kernel over the chunks a step
holds, in place on the stack (``ops/ssm.py`` ``chunk_scan_in_place``;
interpret mode here, under ``jit``: one lowering a shape), against XLA's
``chunk_scan`` composed as the serving forward composes it off the TPU:
the rows gathered by chunk, a chunk's first state cut out of the stack,
its last written back.  The outputs and the states left to float32
rounding, and BIT FOR BIT every row that no run ends in, the trash row
and every other layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssm as M

L, S, LI = 2, 5, 1
F32 = jnp.float32
NONE = (0, 0, S, 1, 0)          # a chunk that is not there


def rnd(k, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(k), shape, dtype)


def xla_chunks(stack, x, b, c, dt, a, d_skip, chunks, fresh, dims):
    """``_ssm_mixer``'s XLA branch under ``ssm_scan`` → (y [T, H, P],
    the stack: a chunk that does not end a run writes the trash row)."""
    T, Q = x.shape[0], dims.chunk
    start, n, slot, first, last = (chunks[:, i] for i in range(5))
    q = jnp.arange(Q)[None, :]
    there = q < n[:, None]
    rows = jnp.minimum(start[:, None] + q, T - 1)
    init = jnp.where(fresh[:, None, None, None], 0,
                     stack[LI][slot].astype(F32))
    y_run, left = M.chunk_scan(
        x[rows], b[rows], c[rows], jnp.where(there[..., None], dt[rows], 0.0),
        a, d_skip, first.astype(bool), init, dims)
    to = jnp.where((n > 0) & (last != 0), slot, S)
    for i in range(chunks.shape[0]):
        stack = stack.at[LI, to[i]].set(left[i].astype(stack.dtype))
    y = jnp.zeros((T,) + y_run.shape[2:], F32).at[
        jnp.where(there, rows, T).reshape(-1)].set(
        y_run.reshape((-1,) + y_run.shape[2:]), mode="drop")
    return y, stack


# both shape classes at toy widths: two groups with whole heads a slab
# (Falcon-H1's), one group with two heads side by side on a slab's lanes
# (granite's); the chunk is 8 rows
TWO_GROUPS = dict(H=4, P=128, G=2)
ONE_GROUP = dict(H=6, P=64, G=1)
# (shape, rows of the step, the table, slots whose run starts from zeros,
# the stored type, heads a grid step takes); a table of six chunks where
# the step has 32 rows, so that cases share their lowered programs
def six(*chunks):
    return list(chunks) + [NONE] * (6 - len(chunks))


CASES = {
    "a chunk of fewer rows than Q": (
        TWO_GROUPS, 32, six((3, 5, 1, 1, 1)), (), F32, None),
    "a run of three chunks carries its state": (
        ONE_GROUP, 32, six((3, 8, 1, 1, 0), (11, 8, 1, 0, 0),
                           (19, 5, 1, 0, 1)), (), F32, None),
    "a run that continues a stored state, in blocks of heads": (
        ONE_GROUP, 32, six((0, 8, 2, 1, 0), (8, 3, 2, 0, 1)), (), F32, 2),
    "a run that is fresh over a dirty slot, in blocks of heads": (
        ONE_GROUP, 32, six((5, 7, 3, 1, 1)), (3,), F32, 2),
    "two runs in one step, one of them fresh": (
        TWO_GROUPS, 32, six((2, 8, 0, 1, 0), (10, 4, 0, 0, 1),
                            (14, 8, 4, 1, 0), (22, 8, 4, 0, 0),
                            (30, 2, 4, 0, 1)), (4,), F32, None),
    "fewer rows in the step than in a chunk": (
        TWO_GROUPS, 6, [(1, 5, 2, 1, 1), NONE], (), F32, None),
    "rows that the chunk does not divide": (
        TWO_GROUPS, 20, [(1, 8, 1, 1, 0), (9, 8, 1, 0, 0), (17, 3, 1, 0, 1),
                         NONE], (), F32, None),
    "the row written back rounded once": (
        ONE_GROUP, 32, six((3, 8, 1, 1, 0), (11, 8, 1, 0, 0),
                           (19, 5, 1, 0, 1)), (), jnp.bfloat16, None),
    "no chunk that is there": (
        ONE_GROUP, 32, six(), (), jnp.bfloat16, None),
}


@functools.lru_cache(maxsize=None)
def programs(shape, hb):
    """ONE jitted call of the kernel and of XLA's form (lowered once
    a shape of the step, the table and the stack)."""
    H, P, G = shape
    dims = M.SSMDims(H * P, H, P, G, 16, 4, 8)
    def kernel(stack, chunks, fresh, x, b, c, *ins):
        # the rows as the convolution leaves them: x, B and C side by side
        xbc = jnp.concatenate([t.reshape(t.shape[0], -1) for t in (x, b, c)],
                              axis=1)
        return M.chunk_scan_in_place(stack, LI, xbc, *ins, chunks, fresh,
                                     dims, hb=hb)

    return (jax.jit(kernel),
            jax.jit(lambda stack, chunks, fresh, *ins: xla_chunks(
                stack, *ins, chunks, fresh, dims)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_xla_chunked_form_and_leaves_the_rest(case):
    shape, T, table, zeros, store, hb = CASES[case]
    H, P, G = shape["H"], shape["P"], shape["G"]
    call, xla = programs((H, P, G), hb)
    stack = rnd(0, (L, S + 1, H, P, 16)).astype(store)
    if zeros:       # a slot taken by a new sequence may hold anything
        stack = stack.at[LI, zeros[0]].set(jnp.nan)
    x, b, c = rnd(1, (T, H, P)), rnd(2, (T, G, 16)), rnd(3, (T, G, 16))
    dt = jax.nn.softplus(rnd(4, (T, H)))
    a, d_skip = -jnp.exp(rnd(5, (H,))), rnd(6, (H,))
    chunks = jnp.asarray(table, jnp.int32)
    fresh = jnp.asarray([s in zeros for _, _, s, _, _ in table])
    ins = (x, b, c, dt, a, d_skip)
    y, new = call(stack, chunks, fresh, *ins)
    y_ref, new_ref = xla(stack, chunks, fresh, *ins)
    top = max(float(jnp.abs(y_ref).max()), 1e-30)
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=1e-5 * top)
    ends = {s for _, n, s, _, last in table if n and last}
    for layer in range(L):
        for slot in range(S + 1):
            got, want = new[layer, slot], new_ref[layer, slot]
            if layer != LI or slot not in ends:
                # the stack comes back bit for bit, the trash row too
                assert (np.asarray(got).tobytes()
                        == np.asarray(stack[layer, slot]).tobytes()), slot
            elif store == F32:
                np.testing.assert_allclose(
                    got, want, rtol=0,
                    atol=1e-5 * float(jnp.abs(want).max()))
            else:
                # rounded ONCE to the stored type: the float32 state's
                # nearest, so off XLA's (which rounds its own float32
                # sums) by one unit in the last place at most
                ulp = np.abs(np.asarray(want, np.float32)) * 2.0 ** -7
                assert (np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32)) <= ulp).all()
                assert (np.asarray(got) == np.asarray(want)).mean() > 0.99
    assert ends or not np.asarray(y).any()
    # rows that no chunk holds read zeros
    held = np.zeros(T, bool)
    for start, n, *_ in table:
        held[start:start + n] = True
    assert not np.asarray(y)[~held].any()


def test_slab_heads_follow_the_shapes():
    """Heads side by side on a slab's 128 lanes: Falcon-H1's heads of
    128 stand alone, granite's of 64 in pairs; a group's heads have to
    divide so, and a width that does not divide 128 stands alone."""
    dims = lambda H, P, G: M.SSMDims(H * P, H, P, G, 128, 4, 128)
    assert M.slab_heads(dims(32, 128, 2)) == 1
    assert M.slab_heads(dims(128, 64, 1)) == 2
    assert M.slab_heads(dims(6, 64, 2)) == 1
    assert M.slab_heads(dims(8, 48, 1)) == 1
    assert M.slab_heads(dims(8, 16, 2)) == 4
