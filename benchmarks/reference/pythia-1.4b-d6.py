"""Plain reference for the Pythia (GPT-NeoX) configurations.

The architecture as published (EleutherAI/pythia-1.4b ``config.json``,
the GPT-NeoX paper, arXiv:2204.06745, section 3.1), written out in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``:
no kernel, no cache, no batching (one sequence at a time), no sharing of
code with ``deepspeed_tpu.models``.  It READS the system's seeded
parameter tree (stacked on a leading layer dimension) and upcasts it.

  x      = embed[ids]
  layer:   x = x + attn(ln1(x)) + mlp(ln2(x))         (parallel residual,
           two LayerNorms, eps 1e-5)
  attn:    q, k, v = ln1(x) W + b; rotary on the first
           rotary_pct * head_dim dims of q and k (rotate-half pairing,
           base 10000); causal softmax(q k^T / sqrt(head_dim)) v; W_o + b
  mlp:     gelu(h W_in + b) W_out + b                  (exact erf gelu)
  logits = ln_f(x) W_head                              (untied)
  loss   = mean over the S-1 next-token targets of every sequence of
           -log softmax(logits)[target]
  update = AdamW (Loshchilov & Hutter): gradient clipped to a global norm,
           bias-corrected moments, decoupled weight decay on every leaf

Departures from the publication: none in the mathematics; dropout is 0
in the published config and is absent here.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _frozen(c):
    """The configuration's plain values as a hashable key, so that each
    program below is built (and compiled) once per configuration."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def _ln(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rotary(x, base, rot):
    """x: [S, H, D]; rotate the first ``rot`` dims, pairing dim i with
    dim i + rot/2."""
    S = x.shape[0]
    inv = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _layer(x, lp, c):
    """One layer on one sequence; ``lp`` holds this layer's tensors in
    whatever type the system keeps them, upcast here."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    a, m = lp["attn"], lp["mlp"]
    S = x.shape[0]
    D = a["wq"].shape[-1]
    h1 = _ln(x, lp["ln1"]["scale"], lp["ln1"]["bias"], c["layer_norm_eps"])
    q = jnp.einsum("sd,dhk->shk", h1, a["wq"]) + a["bq"]
    k = jnp.einsum("sd,dhk->shk", h1, a["wk"]) + a["bk"]
    v = jnp.einsum("sd,dhk->shk", h1, a["wv"]) + a["bv"]
    rot = (int(D * c["rotary_pct"]) // 2) * 2
    q = _rotary(q, c["rotary_emb_base"], rot)
    k = _rotary(k, c["rotary_emb_base"], rot)
    s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v)
    attn = jnp.einsum("qhk,hkd->qd", o, a["wo"]) + a["bo"]
    h2 = _ln(x, lp["ln2"]["scale"], lp["ln2"]["bias"], c["layer_norm_eps"])
    u = jax.nn.gelu(h2 @ m["wi"] + m["bi"], approximate=False)
    return x + attn + (u @ m["wo"] + m["bo"])


def _logits_from(x, params, c):
    lnf = jax.tree.map(lambda a: a.astype(F32), params["ln_f"])
    x = _ln(x, lnf["scale"], lnf["bias"], c["layer_norm_eps"])
    return x @ params["lm_head"]["kernel"].astype(F32)


def _forward(params, ids, c):
    """Whole model on one sequence, as one function (for jax.grad)."""
    x = params["embed"]["table"].astype(F32)[ids]
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[i], params["blocks"]), c)
    return _logits_from(x, params, c)


@functools.lru_cache(maxsize=None)
def _programs(key):
    c = dict(key)
    return {"layer": jax.jit(lambda x, lp: _layer(x, lp, c)),
            "head": jax.jit(lambda x, p: _logits_from(x, p, c)),
            "nll": jax.jit(_nll_sum)}


def logits(params, ids, c):
    """[S] token ids -> [S, vocab] float32, one layer upcast at a time."""
    prog = _programs(_frozen(c))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(F32)
        for i in range(c["num_hidden_layers"]):
            x = prog["layer"](x, jax.tree.map(lambda a: a[i], params["blocks"]))
        return prog["head"](
            x, {"ln_f": params["ln_f"], "lm_head": params["lm_head"]})


def _nll_sum(lg, ids):
    logp = jax.nn.log_softmax(lg[:-1], axis=-1)
    return -jnp.take_along_axis(logp, ids[1:, None], axis=-1).sum()


def loss(params, batch, c):
    """Mean next-token loss of a [B, S] batch, a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        total = 0.0
        nll = _programs(_frozen(c))["nll"]
        for row in batch:
            row = jnp.asarray(row)
            total += float(nll(logits(params, row, c), row))
        return total / (batch.shape[0] * (batch.shape[1] - 1))


def adamw_step(params, batch, c, *, lr, beta1, beta2, eps, weight_decay,
               clip_norm, step=1, forward=_forward):
    """(loss before the update, parameters after ONE AdamW update from
    zero moments); gradient of the batch mean by ``jax.grad``,
    accumulated a sequence at a time.  Gradients and the updated copy
    are kept where each parameter is (one chip, or spread over several
    as the driver made them)."""
    n_targets = batch.shape[0] * (batch.shape[1] - 1)
    where = jax.tree.map(lambda a: a.sharding, params)
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(
            lambda p, ids: _nll_sum(forward(p, ids, c), ids) / n_targets),
            out_shardings=(None, where))
        total, grads = 0.0, None
        for row in batch:
            l, g = vg(params, jnp.asarray(row))
            total += float(l)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)

        @functools.partial(jax.jit, out_shardings=where)
        def update(p, g):
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            g = jax.tree.map(
                lambda x: x * jnp.minimum(1.0, clip_norm / (norm + 1e-6)), g)

            def leaf(p, g):
                m = (1 - beta1) * g / (1 - beta1 ** step)
                v = (1 - beta2) * g * g / (1 - beta2 ** step)
                return p - lr * (m / (jnp.sqrt(v) + eps) + weight_decay * p)
            return jax.tree.map(leaf, p, g)

        return total, update(params, grads)
