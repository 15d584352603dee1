"""The recurrent mixers' one-token state update as a Pallas kernel in
place on the stack (``ops/ssm.py`` ``state_update_in_place``,
``ops/kda.py`` likewise; interpret mode here), against XLA's
``state_update`` over the layer cut out of it: the outputs to float32
rounding, the advanced rows to the stored type's, and BIT FOR BIT
unchanged every row that is not advanced, the trash row and every other
layer.  Then what chooses between the two in the served step, and the
gauge that says how much of the dense update's traffic advanced a row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda as K
from deepspeed_tpu.ops import ssm as M

from test_falcon_h1 import GREEDY, engine as falcon_engine

L, S, H = 3, 6, 4
F32 = jnp.float32

# (active, replay, fresh) by slot: a plain decode batch; every mix of
# the three flags; nothing advanced at all
FLAGS = {
    "decode": ([1, 1, 1, 1, 1, 1], [0] * 6, [0] * 6),
    "mixed": ([1, 1, 0, 1, 1, 0], [0, 1, 0, 0, 1, 0], [0, 0, 0, 1, 1, 1]),
    "idle": ([0] * 6, [0] * 6, [0, 1, 0, 0, 0, 0]),
}


def rnd(k, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(k), shape, dtype)


def flags(name):
    return tuple(jnp.asarray(f, bool) for f in FLAGS[name])


def same_bits(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def check(stack, li, adv, got, want, store):
    """``got``/``want``: (outputs, stack) of the kernel and (outputs,
    layer rows) of XLA's update."""
    (out, new), (out_ref, rows_ref) = got, want
    np.testing.assert_allclose(out, out_ref, rtol=2e-5, atol=2e-5)
    # the advanced rows: the same numbers to the stored type's rounding
    # (a float32 row: to the order its sums were taken in, a few ulps of
    # the row's largest)
    eps = 2.0 ** -8 if store == jnp.bfloat16 else 2.0 ** -20
    want_rows = np.asarray(rows_ref, np.float32)
    np.testing.assert_allclose(
        np.asarray(new[li, :S], np.float32), want_rows, rtol=eps,
        atol=eps * np.abs(want_rows).max())
    for layer in range(L):
        for slot in range(S + 1):
            moved = layer == li and slot < S and bool(adv[slot])
            assert moved or same_bits(new[layer, slot], stack[layer, slot]), \
                (layer, slot)
    # and the advanced rows did move
    for slot in np.flatnonzero(np.asarray(adv)):
        assert not same_bits(new[li, slot], stack[li, slot])


@pytest.mark.parametrize("hb", [None, 2])
@pytest.mark.parametrize("mix", sorted(FLAGS))
@pytest.mark.parametrize("store", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("li", [0, 2])
def test_ssm_kernel_is_the_xla_update_and_leaves_the_rest(li, store, mix, hb):
    P, N, G = 8, 128, 2
    dims = M.SSMDims(H * P, H, P, G, N, 4, 16)
    stack = rnd(0, (L, S + 1, H, P, N)).astype(store)
    x, b, c = rnd(1, (S, H, P)), rnd(2, (S, G, N)), rnd(3, (S, G, N))
    dt = jax.nn.softplus(rnd(4, (S, H)))
    a, d_skip = -jnp.exp(rnd(5, (H,))), rnd(6, (H,))
    active, replay, fresh = flags(mix)
    args = (x, b, c, dt, a, d_skip, active, replay, fresh, dims)
    got = jax.jit(lambda st: M.state_update_in_place(st, li, *args, hb=hb))(
        stack)
    check(stack, li, active & ~replay, got,
          M.state_update(stack[li, :S], *args), store)


@pytest.mark.parametrize("hb", [None, 2])
@pytest.mark.parametrize("mix", sorted(FLAGS))
@pytest.mark.parametrize("store", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("li", [0, 2])
def test_kda_kernel_is_the_xla_update_and_leaves_the_rest(li, store, mix, hb):
    Kd, V = 16, 128
    stack = rnd(0, (L, S + 1, H, Kd, V)).astype(store)
    q, k, v = rnd(1, (S, H, Kd)), rnd(2, (S, H, Kd)), rnd(3, (S, H, V))
    g = -jax.nn.softplus(rnd(4, (S, H, Kd)))
    beta = jax.nn.sigmoid(rnd(5, (S, H)))
    active, replay, fresh = flags(mix)
    args = (q, k, v, g, beta, active, replay, fresh)
    got = jax.jit(lambda st: K.state_update_in_place(st, li, *args, hb=hb))(
        stack)
    check(stack, li, active & ~replay, got,
          K.state_update(stack[li, :S], *args), store)


def test_a_fresh_slot_starts_from_zeros_whatever_it_held():
    """A slot taken by a new sequence may hold anything, a NaN too."""
    Kd, V = 16, 128
    stack = rnd(0, (L, S + 1, H, Kd, V)).at[1, 3].set(jnp.nan)
    q, k, v = rnd(1, (S, H, Kd)), rnd(2, (S, H, Kd)), rnd(3, (S, H, V))
    g, beta = -jax.nn.softplus(rnd(4, (S, H, Kd))), jnp.ones((S, H))
    on = jnp.ones((S,), bool)
    o, new = K.state_update_in_place(stack, 1, q, k, v, g, beta, on, ~on,
                                     jnp.arange(S) == 3)
    assert np.isfinite(np.asarray(o[3])).all()
    # S_1 = beta k v^T from a zero state
    np.testing.assert_allclose(new[1, 3], k[3, :, :, None] * v[3, :, None],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,dtype,want", [
    ((6, 129, 32, 128, 256), jnp.bfloat16, 32),   # falcon-h1-34b-d6: 2 MiB
    ((6, 129, 32, 128, 128), jnp.float32, 32),    # ling-3.0-flash-d7: 2 MiB
    ((2, 9, 64, 128, 256), jnp.float32, 16),      # 8 MiB a row: a quarter
    ((2, 9, 4, 8, 128), jnp.float32, 4),
    ((2, 9, 24, 256, 256), jnp.float32, 24),      # no halving fills 8 rows
])
def test_heads_a_grid_step_takes(shape, dtype, want):
    assert M.heads_per_step(jax.ShapeDtypeStruct(shape, dtype)) == want


def test_head_columns_lays_a_block_s_vectors_out_as_columns():
    a, b = rnd(0, (3, 4, 5)), rnd(1, (3, 4, 5))
    cols = M.head_columns([a, b], 2)
    assert cols.shape == (3, 2, 5, 4)
    for j in range(2):
        for h in range(2):
            np.testing.assert_array_equal(cols[:, j, :, h], a[:, 2 * j + h])
            np.testing.assert_array_equal(cols[:, j, :, 2 + h],
                                          b[:, 2 * j + h])


def _update_calls(eng, scope):
    """The ``pallas_call`` equations of the engine's served step whose
    name stack holds ``scope``."""
    from test_tpu_compile import _eqns
    eng.put(1, [1, 2, 3])
    eng.put(2, [4])
    batch = eng.state.build_batch(eng._schedule(), eng.icfg.token_budget)
    jaxpr = jax.make_jaxpr(eng._build_pstep(None, GREEDY))(
        eng.params, eng._quant, eng.state.kv, batch, eng._zero_toks,
        eng._zero_key)
    return [e for e, _ in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"
            and scope in str(e.source_info.name_stack)]


@pytest.mark.parametrize("preset,scope", [("falcon-h1-tiny", "ssm_update"),
                                          ("ling-tiny", "kda_update")])
def test_the_backend_chooses_the_update_s_path(preset, scope, monkeypatch):
    """Off the TPU the served step's update is XLA's ``state_update``; on
    one, the state rows on one device, the kernel: nothing else decides
    (no option of the engine, no key of the configuration)."""
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.models.transformer import init_params

    cfg = build_config(preset)
    params, axes = init_params(cfg, jax.random.PRNGKey(3))
    assert _update_calls(falcon_engine((cfg, params, axes)), scope) == []
    eng = falcon_engine((cfg, params, axes))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = _update_calls(eng, scope)
    # (a call a place the layers are written out: the scan's body, and
    # with a longer pattern the layers before and behind it), each over
    # the stack in place
    assert calls and all(c.params["input_output_aliases"] == ((3, 0),)
                         for c in calls)


@pytest.mark.parametrize("preset", ["falcon-h1-tiny", "granite-h-tiny"])
def test_the_backend_chooses_the_chunked_form_s_path(preset, monkeypatch):
    """The runs of several tokens likewise: off the TPU XLA's
    ``chunk_scan`` around the state rows cut out of the stack, no kernel
    under ``ssm_scan``; on one, ONE kernel a place the layers are
    written out, over the stack in place."""
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.models.transformer import init_params

    cfg = build_config(preset)
    params, axes = init_params(cfg, jax.random.PRNGKey(3))
    assert _update_calls(falcon_engine((cfg, params, axes)), "ssm_scan") == []
    eng = falcon_engine((cfg, params, axes))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = _update_calls(eng, "ssm_scan")
    assert calls and all(
        c.params["name"] == "ssm_chunk_scan"
        and c.params["input_output_aliases"] == ((6, 0),) for c in calls)
    # and no more of them than the one-token update's
    assert len(calls) == len(_update_calls(
        falcon_engine((cfg, params, axes)), "ssm_update"))


def test_state_update_fill_gauge():
    from deepspeed_tpu.models.presets import build_config
    from deepspeed_tpu.models.transformer import init_params

    cfg = build_config("falcon-h1-tiny")
    params, axes = init_params(cfg, jax.random.PRNGKey(3))
    eng = falcon_engine((cfg, params, axes), token_budget=32)
    assert eng.metrics.snapshot().get("serving_state_update_fill") is None
    eng.put(3, [5])
    eng.put(1, list(range(9)))
    out = eng.step(sampling=GREEDY)
    # one one-token row of the four slots the dense update moved
    assert eng.metrics.snapshot()["serving_state_update_fill"] == 1 / 4
    for u, t in out.items():
        eng.put(u, [t])
    eng.step(sampling=GREEDY)
    assert eng.metrics.snapshot()["serving_state_update_fill"] == 3 / 8
    assert "serving_state_update_fill" in eng.metrics.prometheus_text()
    eng.reset_metrics()
    assert eng.metrics.snapshot().get("serving_state_update_fill") is None
