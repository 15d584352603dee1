"""Driver of a serving mix whose model keeps a delta-rule state for every
sequence in most layers, a latent cache in the rest, and a SHARE of each
expert layer's experts: ``serve.py``, whole, and beside its comparison of
logits the comparisons of both drivers that exist, and one more.

Why.  ``serve_routed.py``: with 512 router outputs, eight chosen, near-
equal sigmoid scores renormalised, a token whose eighth and ninth scores
stand within bfloat16's rounding takes another expert in the system than
in a float32 reference; so the engine says which experts each token took,
the reference FOLLOWS that choice (``following`` in the reference's file)
and everything else is its own, and how far a taken expert's biased score
falls short of the reference's own eighth inside the reference's own kept
groups has its own limit, which a choice made without the group limit
fails.  ``serve_recurrent.py``: a state is advanced and not appended to,
so a prompt cut over several steps has to carry its state and the
convolution's tail across each cut and each chunk, and a slot that a
sequence left must not hand its state on.  And here a state is advanced
thousands of times by a decoding sequence, in its stored type.  So, when
``serve.py`` has made its comparison and the timed loop has not begun,
one sequence at a time through the logits-returning step that also says
the experts each token took, all against the reference that follows:

* ``followed_*``: ``serve.py``'s own three sequences;
* ``chunked_*``: a seeded prompt of ``reference.sample.long_prompt``
  tokens prefilled in the engine's ordinary steps of ``token_budget``
  tokens, then the sample's fed tokens;
* ``long_decode``: a prompt of ``long_decode_prompt`` tokens and
  ``long_decode_tokens`` fed tokens through both caches, the logits
  compared after the last ``decode_tokens`` of them;
* ``reused_slots_*``: the first sample once more, in the slot the others
  left (the free slots are put in order first, so every sequence here
  takes the same row of the state pool after the one before it: the row
  the 4,000-token decode has just left);
* ``routing_shortfall``: the largest shortfall of a taken expert over all
  of these, under its own limit.

It also checks the configuration's keys that ``benchmarks/lib/weights.py``
``transformer_config`` does not know (``CHECKED``: every KDA, MLA, router
and share key), and a published ``head_dim`` that is not ``hidden_size //
num_attention_heads``, which that function would refuse.

``serve.py`` gives no seam for a second comparison: ``run`` is entered
with ``engine_logits`` wrapped, for the one call it makes of it, as the
two other drivers do.

A run has 360 s in the driver's check, set-up, warm-up and window (the
check of PR 44 stopped one there: the comparisons alone took 320 s of a
warm set-up).  So the step is built ONCE (``routing_step``: a step built
anew for each sequence was traced and lowered anew at both of its row
counts, sixteen times a run) and compiled at its row counts side by side;
``serve.py``'s own call gets that step too and builds none of its own;
the three samples have one length and the long prompt with its fed
tokens the long decode's (the configuration's file), so the reference
compiles for two lengths where it compiled for five; only the first
sample goes again; a sequence's reference runs on a thread of its own
behind the next sequences' steps, so that what it compiles for the
samples and the long prompt lies behind the long decode's launches; and
``hybrid_times`` on standard output says what each part took.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import ROOT, load_module, note
from benchmarks.lib.drivers import serve
from benchmarks.lib.drivers.serve_recurrent import left_slots_first
from benchmarks.lib.drivers.serve_routed import follow, without_head_dim

# configuration key -> what the preset has to run for it
CHECKED = {
    "head_dim": lambda c: c.head_dim,
    "first_k_dense_replace": lambda c: c.num_dense_layers,
    "layer_types": lambda c: list(c.layer_kinds),
    "short_conv_kernel_size": lambda c: c.kda_dims.conv,
    "kda_lower_bound": lambda c: c.kda_dims.bound,
    "kv_lora_rank": lambda c: c.mla_dims.kv_rank,
    "qk_nope_head_dim": lambda c: c.mla_dims.nope_dim,
    "qk_rope_head_dim": lambda c: c.mla_dims.rope_dim,
    "qk_head_dim": lambda c: c.mla_dims.nope_dim + c.mla_dims.rope_dim,
    "v_head_dim": lambda c: c.mla_dims.value_dim,
    "rotary_dim": lambda c: c.rotary_dim,
    "partial_rotary_factor": lambda c: c.rope_pct,
    "gated_attention_proj_granularity_type":
        lambda c: {"head": "head_wise"}.get(c.mla_gate),
    "moe_intermediate_size": lambda c: c.moe_d_ff,
    "moe_shared_expert_intermediate_size": lambda c: c.moe_shared_ff,
    "num_shared_experts": lambda c: (c.moe_shared_ff or 0) // c.moe_d_ff,
    "num_experts": lambda c: c.experts_here,
    "router_outputs": lambda c: c.num_experts,
    "experts_held": lambda c: list(c.experts_held
                                   or (0, c.num_experts)),
    "num_experts_per_tok": lambda c: c.moe_top_k,
    "n_group": lambda c: c.moe_groups,
    "topk_group": lambda c: c.moe_groups_kept,
    "score_function": lambda c: c.moe_score,
    "scoring_func": lambda c: c.moe_score,
    "norm_topk_prob": lambda c: c.moe_norm_topk,
    "routed_scaling_factor": lambda c: c.moe_route_scale,
    "moe_router_enable_expert_bias": lambda c: c.moe_select_bias,
}


def check_config(config: dict, cfg):
    if set(cfg.mixer_stacks) != {"kda", "mla"} or cfg.experts_held is None:
        raise SystemExit("the configuration's preset is not one of delta-"
                         "rule and latent layers with a share of its "
                         "experts; this driver is for one that is")
    # num_kv_heads_for_linear_attn 0: as many KDA heads as query heads;
    # their key and value size is what the arithmetic reads as head_dim
    size = config.get("arith", {}).get("head_dim", config.get("head_dim"))
    kd = cfg.kda_dims
    if kd.heads != cfg.num_heads or not kd.key_dim == kd.value_dim == size:
        raise SystemExit(f"the preset's KDA heads ({kd.heads} of "
                         f"{kd.key_dim}/{kd.value_dim}) are not the file's")
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


def routing_step(eng):
    """The logits-returning step that also says the experts each row
    took, built once and compiled at every row count of the engine's
    ladder side by side, a thread each, as the engine builds its serving
    step (``InferenceEngine._compile_rungs``: ``lower().compile()`` fills
    the caches the jit function's own calls read)."""
    step = eng._build_step(eng.max_blocks_per_seq, with_routing=True)
    batches = {rows: eng._stage(eng.state.blank_batch(rows, eng._n_verify))
               for rows in eng._step_rows}
    with ThreadPoolExecutor(len(batches)) as pool:
        list(pool.map(lambda b: step.lower(eng.params, eng._quant,
                                           eng.state.kv, b).compile(),
                      batches.values()))
    return step


def paged_logits(eng, step, tokens, n_prompt: int, keep: int):
    """``serve_routed.paged_logits`` through ``step`` (``routing_step``)
    at the row count the engine would run the step at
    (``InferenceEngine._step_rows``), with nothing read back before the
    sequence ends: a 4,000-token decode is 4,000 steps of one row each,
    which the smallest rung holds and which the host can launch without
    waiting for the one before.  → (the last ``keep`` rows' logits, the
    experts each token took ``[expert layers, tokens, top_k]``, the steps
    it took)."""
    uid, rows, took, fed = 700000, [], [], n_prompt
    eng.put(uid, list(tokens[:n_prompt]))
    while True:
        sched = eng._schedule()
        if not sched:
            break
        (_, chunk), = sched
        n_rows = next(r for r in eng._step_rows if r >= len(chunk))
        batch = eng._stage(eng.state.build_batch(sched, n_rows))
        logits, eng.state.kv, routing = step(eng.params, eng._quant,
                                             eng.state.kv, batch)
        took.append(routing[:, :len(chunk)])
        if eng.state.seqs[uid].seen_tokens >= n_prompt:
            rows = (rows + [logits[eng.state.slot(uid)]])[-keep:]
            if fed < len(tokens):
                eng.put(uid, [int(tokens[fed])])
                fed += 1
    eng.flush(uid)
    return (np.stack([np.asarray(r, np.float32) for r in rows]),
            np.concatenate([np.asarray(t) for t in took], axis=1), len(took))


def sequences(config: dict, eng, seqs: dict, n_prompt: dict, seed: int):
    """The sequences compared, in the order they run → ({name: tokens},
    {name: prompt length})."""
    sample = config["reference"]["sample"]
    k = int(sample["decode_tokens"])
    vocab = eng.cfg.vocab_size
    n_long = int(sample["long_prompt"])
    n_dec, p_dec = int(sample["long_decode_tokens"]), \
        int(sample["long_decode_prompt"])
    out = {f"sample{i}": s for i, s in enumerate(seqs.values())}
    prompts = {f"sample{i}": n for i, n in enumerate(n_prompt.values())}
    out["chunked"] = T.rng_for(seed, 11).integers(
        0, vocab, n_long + k).tolist()
    prompts["chunked"] = n_long
    out["long_decode"] = T.rng_for(seed, 12).integers(
        0, vocab, p_dec + n_dec).tolist()
    prompts["long_decode"] = p_dec
    out["again_sample0"] = out["sample0"]
    prompts["again_sample0"] = prompts["sample0"]
    return out, prompts


def system_side(eng, config: dict, seqs: dict, n_prompt: dict, seed: int,
                step=None, times=None, ready=None):
    """The engine's side of the comparisons → ({name: tokens}, {name:
    prompt length}, {name: (the last rows' logits, the experts each token
    took, the steps it took)}), one sequence at a time, each in the slot
    the one before it left.  ``times``: a dict that takes the seconds
    each sequence took.  ``ready(name, tokens, prompt length, result)``
    is called as each sequence ends."""
    k = int(config["reference"]["sample"]["decode_tokens"])
    named, prompts = sequences(config, eng, seqs, n_prompt, seed)
    step = step or routing_step(eng)
    system = {}
    for name, tokens in named.items():
        t0 = time.monotonic()
        left_slots_first(eng)
        # the long decode is compared at its last rows only
        system[name] = paged_logits(eng, step, tokens, prompts[name], k + 1)
        if times is not None:
            times["system." + name] = time.monotonic() - t0
        if ready is not None:
            ready(name, tokens, prompts[name], system[name])
    if system["chunked"][2] < -(-prompts["chunked"]
                                // eng.icfg.token_budget) + k:
        raise SystemExit("the long prompt was not prefilled over several "
                         "of the engine's steps")
    return named, prompts, system


# the wrong forwards that happen at a position, and where: a state lost
# between the prompt and the fed tokens; a tail lost where the prompt's
# last step began (its end, where one step held it)
AT = {"state_reset": lambda n, budget: n,
      "no_tail": lambda n, budget: (n - 1) // budget * budget or n}


def one_reading(ref, params, config: dict, name: str, tokens, n_prompt: int,
                result, budget: int, wrong=None, times=None) -> dict:
    """One sequence against the reference that follows the engine's
    routing → ``follow``'s entry for it."""
    t0 = time.monotonic()
    if wrong in AT:
        wrong = f"{wrong}@{AT[wrong](n_prompt, budget)}"
    read = follow(ref, params, config, {name: tokens}, {name: result},
                  wrong=wrong)[name]
    if times is not None:
        times["reference." + name] = time.monotonic() - t0
    return read


def summary(read: dict, named: dict, prompts: dict, wrong=None) -> dict:
    """The comparisons' values from the sequences' readings."""
    note("reference_hybrid", read=read, wrong=wrong,
         long_prompt=prompts["chunked"],
         long_decode=len(named["long_decode"]))

    def worst(names, phase):
        return max(read[n][phase] for n in names)

    first = [n for n in read if n.startswith("sample")]
    again = [n for n in read if n.startswith("again_")]
    # the long decode's last rows are all fed tokens: ``follow`` books
    # the first of them as "prefill" and the rest as "decode"
    return {
        "followed_prefill": worst(first, "prefill"),
        "followed_decode": worst(first, "decode"),
        "chunked_prefill": read["chunked"]["prefill"],
        "chunked_decode": read["chunked"]["decode"],
        "long_decode": max(read["long_decode"]["prefill"],
                           read["long_decode"]["decode"]),
        "reused_slots_prefill": worst(again, "prefill"),
        "reused_slots_decode": worst(again, "decode"),
        "routing_shortfall": max(r["short"] for r in read.values()),
    }


def readings(ref, params, config: dict, named: dict, prompts: dict,
             system: dict, budget: int, wrong=None) -> dict:
    """The comparisons' values against the reference that follows the
    engine's routing.  ``wrong``: one of the reference's wrong forwards,
    for the readings that show what the limits refuse."""
    return summary({name: one_reading(ref, params, config, name, named[name],
                                      prompts[name], system[name], budget,
                                      wrong)
                    for name in named}, named, prompts, wrong)


def hybrid_checks(eng, step, config: dict, seqs: dict, n_prompt: dict,
                  seed: int, times: dict) -> dict:
    """``compared`` entries of the comparisons above."""
    refspec = config["reference"]
    tol = refspec["tolerance"]
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_hybrid")
    # a sequence's reference runs behind the next sequences' steps, on
    # a thread of its own: what it compiles (seconds a layer and a
    # length where no compiled program is kept) and computes for the
    # samples and the long prompt lies behind the long decode's 4,000
    # launches, which wait for the device and not for the host
    with ThreadPoolExecutor(1) as behind:
        jobs = {}
        named, prompts, _ = system_side(
            eng, config, seqs, n_prompt, seed, step, times,
            ready=lambda name, tokens, n, result: jobs.update({
                name: behind.submit(one_reading, ref, eng.model.params,
                                    config, name, tokens, n, result,
                                    eng.icfg.token_budget, None, times)}))
        t0 = time.monotonic()
        read = {name: job.result() for name, job in jobs.items()}
        times["reference.after_the_last_step"] = time.monotonic() - t0
    got = summary(read, named, prompts)
    return {name: {"system": value, "reference": 0.0, "rel": value,
                   "tol": limit, "ok": bool(value <= limit)}
            for name, value in got.items()
            for limit in (tol["routing_short" if name == "routing_shortfall"
                              else "followed_rel"],)}


def run(ctx):
    config = ctx["config"]
    preset_config(config)
    checks = {}
    engine_logits = serve.engine_logits

    def and_hybrid(eng, seqs, n_prompt, mbs):
        times, t0 = {}, time.monotonic()
        step = routing_step(eng)
        times["routing_step"] = time.monotonic() - t0
        # serve.py's own comparison (the samples' prompts prefilled
        # together in one step, the reference's own choice of experts)
        # through the same step: it reads two outputs of the step it
        # builds, so it is handed this one's first two and builds none
        with mock.patch.object(
                eng, "_build_step",
                lambda mbs: lambda *a: step(*a)[:2]):
            got = engine_logits(eng, seqs, n_prompt, mbs)
        times["serve.engine_logits"] = time.monotonic() - t0 \
            - times["routing_step"]
        checks.update(hybrid_checks(eng, step, config, seqs, n_prompt,
                                    ctx["args"].seed, times))
        note("hybrid_times", seconds={k: round(v, 2)
                                      for k, v in times.items()})
        return got

    with mock.patch.object(serve, "engine_logits", and_hybrid):
        rec = serve.run({**ctx, "config": without_head_dim(config)})
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    return rec
