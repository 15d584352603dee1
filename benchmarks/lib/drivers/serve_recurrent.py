"""Driver of a serving mix whose model keeps a recurrent state for every
sequence beside its keys and values: ``serve.py``, whole, and beside its
comparison of logits two more that the state makes necessary.

Why.  ``serve.py`` prefills three seeded prompts in ONE step and feeds 8
tokens through the caches.  A recurrent state is advanced and not
appended to, so what can go wrong with it lies where that sample never
goes: a prompt cut over several steps has to carry its state and the
convolution's tail across each cut and across the chunks of the chunked
form inside a step; and a slot that a sequence left still holds that
sequence's last state, which the next one to take the slot must not
read.  So here, when ``serve.py`` has made its comparison and the timed
loop has not begun:

* ``chunked_*``: a seeded prompt of ``reference.sample.long_prompt``
  tokens (several steps' worth) prefilled in the engine's ordinary steps
  of ``token_budget`` tokens, then the sample's fed tokens, against the
  reference's full forward;
* ``reused_slots_*``: the first sample once more after all of these
  sequences ended, in the slots they left (the free slots are put in
  order first, so that the same rows of the state pool are taken again).

All under the one tolerance of the configuration's file.

It also checks the configuration's keys that ``benchmarks/lib/weights.py``
``transformer_config`` does not know (``CHECKED``: every ``mamba_*`` key
and every multiplier), and a published ``head_dim`` that is not
``hidden_size // num_attention_heads``, which that function would
refuse.

``serve.py`` gives no seam for a second comparison: ``run`` is entered
with ``engine_logits`` wrapped, for the one call it makes of it, as
``serve_routed.py`` does.
"""

import os
from unittest import mock

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import ROOT, load_module, note
from benchmarks.lib.drivers import serve
from benchmarks.lib.drivers.serve_routed import rel, without_head_dim

# configuration key -> what the preset has to run for it
CHECKED = {
    "head_dim": lambda c: c.head_dim,
    "mamba_d_ssm": lambda c: c.ssm_d,
    "mamba_n_heads": lambda c: c.ssm_heads,
    "mamba_d_head": lambda c: c.ssm_head_dim,
    "mamba_n_groups": lambda c: c.ssm_groups,
    "mamba_d_state": lambda c: c.ssm_state,
    "mamba_d_conv": lambda c: c.ssm_conv,
    "mamba_chunk_size": lambda c: c.ssm_chunk,
    "embedding_multiplier": lambda c: c.embed_scale,
    "lm_head_multiplier": lambda c: c.head_scale,
    "attention_in_multiplier": lambda c: c.attn_in_scale,
    "attention_out_multiplier": lambda c: c.attn_out_scale,
    "key_multiplier": lambda c: c.key_scale,
    "ssm_in_multiplier": lambda c: c.ssm_in_scale,
    "ssm_out_multiplier": lambda c: c.ssm_out_scale,
    "mlp_multipliers": lambda c: [c.mlp_gate_scale, c.mlp_out_scale],
    "ssm_multipliers": lambda c: list(c.ssm_col_scales),
}


def check_config(config: dict, cfg):
    if not cfg.has_ssm:
        raise SystemExit("the configuration's preset holds no recurrent "
                         "layer; this driver is for one that does")
    for key, runs in CHECKED.items():
        if key in config and config[key] != runs(cfg):
            raise SystemExit(f"configuration file says {key}={config[key]}, "
                             f"the system would run {runs(cfg)}")


def preset_config(config: dict):
    """The preset the file names, checked against the file by the
    harness's function and by ``check_config``."""
    from benchmarks.lib.weights import transformer_config
    cfg = transformer_config(without_head_dim(config))
    check_config(config, cfg)
    return cfg


def paged_logits(eng, tokens, n_prompt: int):
    """One sequence through the engine's paged path: the first
    ``n_prompt`` tokens in the scheduler's ordinary steps of
    ``token_budget`` tokens, the rest fed one at a time.  → (rows, row i
    being the logits after token ``n_prompt - 1 + i``; the steps it
    took)."""
    step = eng._build_step(eng.max_blocks_per_seq)
    uid, rows, fed, steps = 700000, [], n_prompt, 0
    eng.put(uid, list(tokens[:n_prompt]))
    while True:
        sched = eng._schedule()
        if not sched:
            break
        steps += 1
        batch = eng._stage(eng.state.build_batch(sched,
                                                 eng.icfg.token_budget))
        logits, eng.state.kv = step(eng.params, eng._quant, eng.state.kv,
                                    batch)
        if eng.state.seqs[uid].seen_tokens >= n_prompt:
            rows.append(np.asarray(logits[eng.state.slot(uid)], np.float32))
            if fed < len(tokens):
                eng.put(uid, [int(tokens[fed])])
                fed += 1
    eng.flush(uid)
    return np.stack(rows), steps


def left_slots_first(eng):
    """The free slots in order, so that the next sequences take the rows
    of the state pool that the last ones left (the engine hands out
    slots first freed, first taken: of 128 the same three would come
    round again only after 125 others)."""
    eng.state._free_slots.sort()


def recurrent_checks(eng, engine_logits, config: dict, seqs: dict,
                     n_prompt: dict, mbs: int, seed: int) -> dict:
    """``compared`` entries of the two comparisons above."""
    refspec = config["reference"]
    tol = refspec["tolerance"]["logits_rel"]
    sample = refspec["sample"]
    k = int(sample["decode_tokens"])
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_recurrent")
    params = eng.model.params

    def want(tokens):
        return np.asarray(ref.logits(params, np.asarray(tokens), config,
                                     last=k + 1), np.float32)

    n_long = int(sample["long_prompt"])
    long_seq = T.rng_for(seed, 11).integers(
        0, eng.cfg.vocab_size, n_long + k).tolist()
    left_slots_first(eng)
    got, steps = paged_logits(eng, long_seq, n_long)
    if steps < -(-n_long // eng.icfg.token_budget) + k or \
            n_long <= eng.icfg.token_budget:
        raise SystemExit("the long prompt was not prefilled over several "
                         "of the engine's steps")
    far = want(long_seq)
    left_slots_first(eng)
    slots_before = sorted(eng.state._free_slots)[:len(seqs)]
    again = engine_logits(eng, seqs, n_prompt, mbs)
    wants = {u: want(s) for u, s in seqs.items()}
    read = {"chunked_prefill": rel(got[:1], far[:1]),
            "chunked_decode": rel(got[1:], far[1:]),
            "reused_slots_prefill": max(
                rel(np.stack(again[u][:1]), wants[u][:1]) for u in seqs),
            "reused_slots_decode": max(
                rel(np.stack(again[u][1:]), wants[u][1:]) for u in seqs)}
    note("reference_recurrent", long_prompt=n_long, steps=steps,
         slots_taken_again=slots_before, read=read)
    return {name: {"system": v, "reference": 0.0, "rel": v, "tol": tol,
                   "ok": bool(v <= tol)} for name, v in read.items()}


def run(ctx):
    config = ctx["config"]
    preset_config(config)
    checks = {}
    engine_logits = serve.engine_logits

    def and_recurrent(eng, seqs, n_prompt, mbs):
        got = engine_logits(eng, seqs, n_prompt, mbs)
        checks.update(recurrent_checks(eng, engine_logits, config, seqs,
                                       n_prompt, mbs, ctx["args"].seed))
        return got

    with mock.patch.object(serve, "engine_logits", and_recurrent):
        rec = serve.run({**ctx, "config": without_head_dim(config)})
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    return rec
