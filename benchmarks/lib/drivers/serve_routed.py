"""Driver of a serving mix whose model routes tokens to a few of many
near-equally weighted experts: ``serve.py``, whole, and beside its
comparison of logits a second one that FOLLOWS the system's routing.

Why.  ``serve.py`` compares the engine's logits with the reference's as
the largest difference over the sample's rows.  With 128 experts, eight
chosen, sigmoid scores renormalised, a token whose eighth and ninth
scores stand within bfloat16's rounding takes another expert in the
system than in a float32 reference, which moves a third of that layer's
output: a row that holds a swap reads 0.1-0.3 of max|reference| for
that alone where a row without one reads 0.02, a sample's largest row
read 0.17-0.33 on the chip, and a maximum over rows cannot tell int8
weights or a wrong position encoding from a sound bfloat16 forward
(PERF.md section 6, PR 38).  A swap between near-tied experts is the
router's published behaviour under rounding, not an error; so here the
engine also says which experts each token took
(``InferenceEngine._build_step(with_routing=True)``), the reference
computes its forward with that choice given and everything else its own
(``following`` in the reference's file), and the rows are compared under
a limit that a rounding of the weights to int8 fails.  What a given
choice could hide is checked too: how far a taken expert's score falls
short of the reference's own eighth has its own limit, which a choice
made by another rule fails.

The sample is ``serve.py``'s own three sequences and one prompt of a
window and a half, prefilled in the engine's ordinary chunks of
``token_budget`` tokens, so the comparison also sees the window cut keys
(``serve.py``'s ``engine_logits`` needs a prompt to fit one step).

It also checks the configuration's keys that
``benchmarks/lib/weights.py`` ``transformer_config`` does not know
(``CHECKED``), and a published ``head_dim`` that is not ``hidden_size //
num_attention_heads``, which that function would refuse.

``serve.py`` gives no seam for a second comparison: ``run`` is entered
with ``engine_logits`` wrapped, for the one call it makes of it, when
the engine exists and the timed loop has not begun.
"""

import os
from unittest import mock

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import ROOT, load_module, note
from benchmarks.lib.drivers import serve

KINDS = {"window": "sliding_attention", "full": "full_attention"}
# configuration key -> what the preset has to run for it
CHECKED = {
    "head_dim": lambda c: c.head_dim,
    "moe_intermediate_size": lambda c: c.moe_d_ff,
    "num_experts": lambda c: c.num_experts,
    "num_experts_per_tok": lambda c: c.moe_top_k,
    "num_shared_experts": lambda c: (c.moe_shared_ff or 0) // c.moe_d_ff,
    "num_dense_layers": lambda c: c.num_dense_layers,
    "sliding_window": lambda c: c.attn_window,
    "score_func": lambda c: c.moe_score,
    "route_norm": lambda c: c.moe_norm_topk,
    "route_scale": lambda c: c.moe_route_scale,
    "layer_types": lambda c: [KINDS[k] for k in c.layer_kinds],
}


def check_config(config: dict, cfg):
    for key, runs in CHECKED.items():
        if key in config and config[key] != runs(cfg):
            raise SystemExit(f"configuration file says {key}={config[key]}, "
                             f"the system would run {runs(cfg)}")


def without_head_dim(config: dict) -> dict:
    """The file as ``weights.transformer_config`` takes it: that function
    holds a top-level head_dim to hidden_size // num_attention_heads."""
    return {k: v for k, v in config.items() if k != "head_dim"}


def preset_config(config: dict):
    """The preset the file names, checked against the file by the
    harness's function and by ``check_config``."""
    from benchmarks.lib.weights import transformer_config
    cfg = transformer_config(without_head_dim(config))
    check_config(config, cfg)
    return cfg


def paged_logits(eng, tokens, n_prompt: int):
    """One sequence through the engine's paged path: the first
    ``n_prompt`` tokens in the scheduler's ordinary chunks of
    ``token_budget`` tokens, the rest fed one at a time.  → (rows, row i
    being the logits after token ``n_prompt - 1 + i``; the experts each
    token took ``[expert layers, tokens, top_k]``; the steps it took)."""
    step = eng._build_step(eng.max_blocks_per_seq, with_routing=True)
    uid, rows, took, fed = 700000, [], [], n_prompt
    eng.put(uid, list(tokens[:n_prompt]))
    while True:
        sched = eng._schedule()
        if not sched:
            break
        (_, chunk), = sched
        batch = eng._stage(eng.state.build_batch(sched,
                                                 eng.icfg.token_budget))
        logits, eng.state.kv, routing = step(eng.params, eng._quant,
                                             eng.state.kv, batch)
        took.append(np.asarray(routing)[:, :len(chunk)])
        if eng.state.seqs[uid].seen_tokens >= n_prompt:
            rows.append(np.asarray(logits[eng.state.slot(uid)], np.float32))
            if fed < len(tokens):
                eng.put(uid, [int(tokens[fed])])
                fed += 1
    eng.flush(uid)
    return np.stack(rows), np.concatenate(took, axis=1), len(took)


def rel(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def system_side(eng, seqs: dict, n_prompt: dict) -> dict:
    """``paged_logits`` of ``seqs`` ({name: tokens}), one sequence at a
    time."""
    eng.state.reset_prefix_cache()
    out = {name: paged_logits(eng, tokens, n_prompt[name])
           for name, tokens in seqs.items()}
    eng.state.reset_prefix_cache()
    return out


def follow(ref, params, config: dict, seqs: dict, system: dict,
           wrong=None) -> dict:
    """``system_side``'s logits against the reference that follows its
    routing → {name: {prefill, decode, short, steps}}: the two readings
    of ``serve.py``'s comparison, and the largest amount by which a taken
    expert's score falls short of the reference's own eighth.
    ``wrong``: one of the reference's wrong forwards."""
    out = {}
    for name, (got, took, steps) in system.items():
        want, short = ref.following(params, np.asarray(seqs[name]), config,
                                    took, wrong=wrong, last=len(got))
        want = np.asarray(want, np.float32)
        out[name] = {"prefill": rel(got[:1], want[:1]),
                     "decode": rel(got[1:], want[1:]), "short": short,
                     "steps": steps}
    return out


def past_window(config: dict, cfg, seed: int):
    """A seeded sequence of a window and a half and the sample's fed
    tokens → (tokens, prompt length)."""
    k = int(config["reference"]["sample"]["decode_tokens"])
    n = cfg.attn_window + cfg.attn_window // 2
    return T.rng_for(seed, 11).integers(0, cfg.vocab_size, n + k).tolist(), n


def followed_checks(eng, config: dict, seqs: dict, n_prompt: dict,
                    seed: int) -> dict:
    """``compared`` entries of the comparison that follows the routing."""
    refspec = config["reference"]
    tol = refspec["tolerance"]
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_followed")
    long_seq, n_long = past_window(config, eng.cfg, seed)
    seqs = {**seqs, "past_window": long_seq}
    read = follow(ref, eng.model.params, config, seqs, system_side(
        eng, seqs, {**n_prompt, "past_window": n_long}))
    far = read.pop("past_window")
    if far["steps"] < -(-n_long // eng.icfg.token_budget) + len(long_seq) \
            - n_long:
        raise SystemExit("the prompt past the window was not prefilled in "
                         "the engine's chunks")
    note("reference_followed", sample=read, past_window=far)

    def entry(value, limit):
        return {"system": value, "reference": 0.0, "rel": value,
                "tol": limit, "ok": bool(value <= limit)}

    return {
        "followed_prefill": entry(max(r["prefill"] for r in read.values()),
                                  tol["followed_rel"]),
        "followed_decode": entry(max(r["decode"] for r in read.values()),
                                 tol["followed_rel"]),
        "past_window_prefill": entry(far["prefill"], tol["followed_rel"]),
        "past_window_decode": entry(far["decode"], tol["followed_rel"]),
        "routing_shortfall": entry(
            max([far["short"]] + [r["short"] for r in read.values()]),
            tol["routing_short"]),
    }


def run(ctx):
    config = ctx["config"]
    preset_config(config)
    checks = {}
    engine_logits = serve.engine_logits

    def and_followed(eng, seqs, n_prompt, mbs):
        got = engine_logits(eng, seqs, n_prompt, mbs)
        checks.update(followed_checks(eng, config, seqs, n_prompt,
                                      ctx["args"].seed))
        return got

    with mock.patch.object(serve, "engine_logits", and_followed):
        rec = serve.run({**ctx, "config": without_head_dim(config)})
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    return rec
