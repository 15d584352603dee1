"""Driver of a serving mix whose model has latent attention in every
layer, the latent pool as its ONLY cache, and a SHARE of each expert
layer's experts behind a router that also chooses experts which compute
nothing: ``serve.py``, whole, and beside its comparison of logits the
comparisons of ``serve_hybrid_share.py`` that such a model has.

Why a driver of its own: ``serve_hybrid_share.py`` is for a model of
delta-rule AND latent layers (it checks KDA's keys and decodes 4,000
tokens through a state).  Here nothing is advanced: a token leaves a row
in each latent sublayer and every later token reads it by block table.
What can go wrong is the cut of a long prompt over steps and chunks, the
pool's blocks, the query latent and the two constant multipliers, the
router's choice among 768 softmax scores (a twelfth and a thirteenth
within bfloat16's rounding swap, as in ``serve_routed.py``) and where
the expert output joins the stream.  So, when ``serve.py`` has made its
comparison (three seeded prompts prefilled together, the reference's
OWN choice of experts: a gross check) and the timed loop has not begun,
one sequence at a time through the logits-returning step that also says
the experts each token took, all against the reference that FOLLOWS:

* ``followed_*``: ``serve.py``'s own three sequences;
* ``chunked_*``: a seeded prompt of ``reference.sample.long_prompt``
  tokens prefilled in the engine's ordinary steps of ``token_budget``
  tokens (the latent rows of the earlier steps read by block table, a
  step's run cut into chunks), then the sample's fed tokens;
* ``routing_shortfall``: the largest amount by which a taken expert's
  biased score falls short of the reference's own twelfth, as a share of
  that twelfth, over all of these, under its own limit.

It also checks the configuration's keys that ``benchmarks/lib/weights.py``
``transformer_config`` does not know (``CHECKED``).  The step is built
once and compiled at its row counts side by side
(``serve_hybrid_share.routing_step``); ``serve.py``'s own call gets that
step too; a sequence's reference runs on a thread behind the next
sequence's steps; ``latent_times`` on standard output says what each
part took.
"""

import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

from benchmarks.lib import traffic as T
from benchmarks.lib.common import ROOT, load_module, note
from benchmarks.lib.drivers import serve
from benchmarks.lib.drivers.serve_hybrid_share import (one_reading,
                                                       paged_logits,
                                                       routing_step)

# configuration key -> what the preset has to run for it
CHECKED = {
    "num_layers": lambda c: c.num_layers // len(c.layer_pattern),
    "ffn_hidden_size": lambda c: c.d_ff,
    "expert_ffn_hidden_size": lambda c: c.moe_d_ff,
    "attention_bias": lambda c: c.attn_bias,
    "attention_method": lambda c: {("mla",): "MLA"}.get(c.mixer_stacks),
    "kv_lora_rank": lambda c: c.mla_dims.kv_rank,
    "q_lora_rank": lambda c: c.mla_dims.q_rank,
    "qk_nope_head_dim": lambda c: c.mla_dims.nope_dim,
    "qk_rope_head_dim": lambda c: c.mla_dims.rope_dim,
    "v_head_dim": lambda c: c.mla_dims.value_dim,
    "mla_scale_q_lora": lambda c: math.isclose(
        c.mla_dims.q_scale, math.sqrt(c.d_model / c.mla_dims.q_rank))
    and c.mla_dims.q_scale != 1.0,
    "mla_scale_kv_lora": lambda c: math.isclose(
        c.mla_dims.kv_scale, math.sqrt(c.d_model / c.mla_dims.kv_rank))
    and c.mla_dims.kv_scale != 1.0,
    "n_routed_experts": lambda c: c.experts_here,
    "router_outputs": lambda c: c.router_outputs,
    "experts_held": lambda c: list(c.experts_held or (0, c.num_experts)),
    "zero_expert_num": lambda c: c.moe_zero_experts,
    "zero_expert_type": lambda c: "identity",
    "moe_topk": lambda c: c.moe_top_k,
    "routed_scaling_factor": lambda c: c.moe_route_scale,
}


def check_config(config: dict, cfg):
    if cfg.mixer_stacks != ("mla",) or not cfg.moe_shortcut \
            or cfg.experts_held is None or not cfg.moe_zero_experts \
            or cfg.moe_norm_topk or cfg.moe_score != "softmax":
        raise SystemExit("the configuration's preset is not one of latent "
                         "layers alone with a shortcut-connected share of "
                         "its experts; this driver is for one that is")
    for key, runs in CHECKED.items():
        if key in config and config[key] != runs(cfg):
            raise SystemExit(f"configuration file says {key}={config[key]}, "
                             f"the system would run {runs(cfg)}")


def preset_config(config: dict):
    """The preset the file names, checked against the file by the
    harness's function and by ``check_config``."""
    from benchmarks.lib.weights import transformer_config
    cfg = transformer_config(config)
    check_config(config, cfg)
    return cfg


def sequences(config: dict, eng, seqs: dict, n_prompt: dict, seed: int):
    """The sequences compared, in the order they run → ({name: tokens},
    {name: prompt length})."""
    sample = config["reference"]["sample"]
    k, n_long = int(sample["decode_tokens"]), int(sample["long_prompt"])
    out = {f"sample{i}": s for i, s in enumerate(seqs.values())}
    prompts = {f"sample{i}": n for i, n in enumerate(n_prompt.values())}
    out["chunked"] = T.rng_for(seed, 11).integers(
        0, eng.cfg.vocab_size, n_long + k).tolist()
    prompts["chunked"] = n_long
    return out, prompts


def summary(read: dict, prompts: dict, wrong=None) -> dict:
    """The comparisons' values from the sequences' readings."""
    note("reference_latent", read=read, wrong=wrong,
         long_prompt=prompts["chunked"])
    first = [n for n in read if n.startswith("sample")]
    return {
        "followed_prefill": max(read[n]["prefill"] for n in first),
        "followed_decode": max(read[n]["decode"] for n in first),
        "chunked_prefill": read["chunked"]["prefill"],
        "chunked_decode": read["chunked"]["decode"],
        "routing_shortfall": max(r["short"] for r in read.values()),
    }


def system_side(eng, step, config: dict, seqs: dict, n_prompt: dict,
                seed: int, times=None, ready=None):
    """The engine's side → ({name: tokens}, {name: prompt length}, {name:
    (the last rows' logits, the experts each token took, the steps it
    took)}), one sequence at a time.  ``ready(name, tokens, prompt
    length, result)`` is called as each sequence ends."""
    k = int(config["reference"]["sample"]["decode_tokens"])
    named, prompts = sequences(config, eng, seqs, n_prompt, seed)
    system = {}
    eng.state.reset_prefix_cache()
    for name, tokens in named.items():
        t0 = time.monotonic()
        system[name] = paged_logits(eng, step, tokens, prompts[name], k + 1)
        if times is not None:
            times["system." + name] = time.monotonic() - t0
        if ready is not None:
            ready(name, tokens, prompts[name], system[name])
    eng.state.reset_prefix_cache()
    if system["chunked"][2] < -(-prompts["chunked"]
                                // eng.icfg.token_budget) + k:
        raise SystemExit("the long prompt was not prefilled over several "
                         "of the engine's steps")
    return named, prompts, system


def readings(ref, params, config: dict, named: dict, prompts: dict,
             system: dict, budget: int, wrong=None) -> dict:
    """The comparisons' values against the reference that follows the
    engine's routing.  ``wrong``: one of the reference's wrong forwards,
    for the readings that show what the limits refuse."""
    return summary({name: one_reading(ref, params, config, name, named[name],
                                      prompts[name], system[name], budget,
                                      wrong)
                    for name in named}, prompts, wrong)


def latent_checks(eng, step, config: dict, seqs: dict, n_prompt: dict,
                  seed: int, times: dict) -> dict:
    """``compared`` entries of the comparisons above."""
    refspec = config["reference"]
    tol = refspec["tolerance"]
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_latent")
    with ThreadPoolExecutor(1) as behind:
        jobs = {}
        _, prompts, _ = system_side(
            eng, step, config, seqs, n_prompt, seed, times,
            ready=lambda name, tokens, n, result: jobs.update({
                name: behind.submit(one_reading, ref, eng.model.params,
                                    config, name, tokens, n, result,
                                    eng.icfg.token_budget, None, times)}))
        t0 = time.monotonic()
        read = {name: job.result() for name, job in jobs.items()}
        times["reference.after_the_last_step"] = time.monotonic() - t0
    return {name: {"system": value, "reference": 0.0, "rel": value,
                   "tol": limit, "ok": bool(value <= limit)}
            for name, value in summary(read, prompts).items()
            for limit in (tol["routing_short" if name == "routing_shortfall"
                              else "followed_rel"],)}


def run(ctx):
    config = ctx["config"]
    preset_config(config)
    checks = {}
    engine_logits = serve.engine_logits

    def and_latent(eng, seqs, n_prompt, mbs):
        times, t0 = {}, time.monotonic()
        step = routing_step(eng)
        times["routing_step"] = time.monotonic() - t0
        # serve.py's own comparison through the same step: it reads two
        # outputs of the step it builds, so it is handed this one's
        # first two and builds none
        with mock.patch.object(
                eng, "_build_step",
                lambda mbs: lambda *a: step(*a)[:2]):
            got = engine_logits(eng, seqs, n_prompt, mbs)
        times["serve.engine_logits"] = time.monotonic() - t0 \
            - times["routing_step"]
        checks.update(latent_checks(eng, step, config, seqs, n_prompt,
                                    ctx["args"].seed, times))
        note("latent_times", seconds={k: round(v, 2)
                                      for k, v in times.items()})
        return got

    with mock.patch.object(serve, "engine_logits", and_latent):
        rec = serve.run(ctx)
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    return rec
