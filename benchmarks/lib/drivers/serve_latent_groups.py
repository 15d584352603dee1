"""Driver of a serving mix whose model has latent attention under YaRN in
every layer, the latent pool as its ONLY cache WITH a prefix cache over
it, and a share of WHOLE device groups of its routed experts behind a
group-limited router, under traffic that asks one long document several
times: ``serve.py``, whole, and beside its comparison of logits the
comparisons of ``serve_latent_share.py``, two that such traffic needs,
and the documents' first prefill.

What it adds to ``serve.py``, and why.  The reference FOLLOWS the experts
the engine took (``serve_routed.py``: with 160 router outputs and six
chosen, a sixth and a seventh score within bfloat16's rounding swap, which
is the router's published behaviour under rounding and no error), and how
far a taken expert falls short of the reference's own sixth INSIDE ITS OWN
OPEN GROUPS, and its group short of the last open group, has a limit of
its own, which a choice made by the other group rule fails.  So, when
``serve.py`` has made its comparison (three seeded prompts prefilled
together, the reference's OWN choice: a gross check) and the timed loop
has not begun, one sequence at a time through the logits-returning step
that also says the experts each token took:

* ``followed_*``: ``serve.py``'s own three sequences;
* ``chunked_*``: a seeded prompt of ``reference.sample.long_prompt``
  tokens (the traffic's own length: a document and a question, four times
  YaRN's original 4,096 positions) prefilled in the engine's ordinary
  steps of ``token_budget`` tokens, then the sample's fed tokens;
* ``shared_prefix_*``: a SECOND sequence whose first
  ``shared_prefix_tokens`` tokens (the traffic's document) are the long
  prompt's, admitted onto the blocks that one left indexed: its ``cached_tokens`` must be that whole
  prefix (the run stops otherwise), it prefills only its own tokens over
  the aliased rows, and it must read as the reference's full forward of
  all its tokens does (the aliased tokens' routing is the long prompt's
  run's: those rows were computed there);
* ``reused_slots_*``: the first sample once more with the index emptied,
  in the slot and the blocks the others left;
* ``routing_shortfall``: the largest shortfall over all of these.

The traffic's hot documents (``shared_prefix`` of the mix: the same token
ids the load generator will send) are prefilled once, when the engine is
warm and the gateway does not exist yet, and left indexed: the load then
starts in steady state, three admissions in four a hit.  That is set-up,
and ``setup_s`` counts it.

It checks the configuration's keys that ``benchmarks/lib/weights.py``
``transformer_config`` does not know (``CHECKED``), and in a traced run
prints ``shareddocs_step_parts``: the cell's own readings by
``benchmarks/lib/arith_dsv2.py``, which would be per-layer metrics if
``BENCHMARK.json`` had room for their entries (it holds 128 of 128).

``serve.py`` gives no seam for any of this: ``run`` is entered with
``engine_logits`` wrapped, for the one call it makes of it, and with
``deepspeed_tpu.gateway.spawn_gateway`` wrapped, which it calls when the
engine is warm.  ``latent_groups_times`` on standard output says what
each part took.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

import numpy as np

from benchmarks.lib import traffic as T
from benchmarks.lib.common import ROOT, load_module, note
from benchmarks.lib.drivers import serve
from benchmarks.lib.drivers.serve_hybrid_share import (one_reading,
                                                       paged_logits,
                                                       routing_step)
from benchmarks.lib.drivers.serve_recurrent import left_slots_first

RULES = {"max": "group_limited_greedy", "top2": "noaux_tc"}
YARN = {"factor": "factor", "original_max_position_embeddings": "original",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow",
        "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}
# configuration key -> what the preset has to run for it
CHECKED = {
    "attention_bias": lambda c: c.attn_bias,
    "first_k_dense_replace": lambda c: c.num_dense_layers,
    "moe_layer_freq": lambda c: len(c.layer_pattern),
    "kv_lora_rank": lambda c: c.mla_dims.kv_rank,
    "q_lora_rank": lambda c: c.mla_dims.q_rank,
    "qk_nope_head_dim": lambda c: c.mla_dims.nope_dim,
    "qk_rope_head_dim": lambda c: c.mla_dims.rope_dim,
    "v_head_dim": lambda c: c.mla_dims.value_dim,
    "moe_intermediate_size": lambda c: c.moe_d_ff,
    "n_shared_experts": lambda c: (c.moe_shared_ff or 0) // c.moe_d_ff,
    "n_routed_experts": lambda c: c.experts_here,
    "router_outputs": lambda c: c.router_outputs,
    "experts_held": lambda c: list(c.experts_held or (0, c.num_experts)),
    "num_experts_per_tok": lambda c: c.moe_top_k,
    "n_group": lambda c: c.moe_groups,
    "topk_group": lambda c: c.moe_groups_kept,
    "topk_method": lambda c: RULES[c.moe_group_score],
    "scoring_func": lambda c: c.moe_score,
    "norm_topk_prob": lambda c: c.moe_norm_topk,
    "routed_scaling_factor": lambda c: c.moe_route_scale,
    "rope_scaling": lambda c: {
        "type": "yarn", **{k: getattr(c.rope_yarn, f)
                           for k, f in YARN.items()}},
}


def check_config(config: dict, cfg):
    if cfg.mixer_stacks != ("mla",) or cfg.moe_shortcut \
            or cfg.held_groups is None or cfg.rope_yarn is None \
            or cfg.moe_zero_experts or cfg.moe_shared_gate \
            or (cfg.mla_dims.q_scale, cfg.mla_dims.kv_scale) != (1.0, 1.0):
        raise SystemExit("the configuration's preset is not one of latent "
                         "layers under YaRN with a share of whole device "
                         "groups of its experts; this driver is for one "
                         "that is")
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
    n_shared = int(sample["shared_prefix_tokens"])
    vocab = eng.cfg.vocab_size
    out = {f"sample{i}": s for i, s in enumerate(seqs.values())}
    prompts = {f"sample{i}": n for i, n in enumerate(n_prompt.values())}
    out["chunked"] = T.rng_for(seed, 11).integers(
        0, vocab, n_long + k).tolist()
    # one length with the long prompt: the reference compiles a length
    out["shared"] = out["chunked"][:n_shared] + T.rng_for(seed, 13).integers(
        0, vocab, n_long + k - n_shared).tolist()
    out["again_sample0"] = out["sample0"]
    prompts.update(chunked=n_long, shared=n_long,
                   again_sample0=prompts["sample0"])
    return out, prompts


def system_side(eng, step, config: dict, seqs: dict, n_prompt: dict,
                seed: int, times=None, ready=None):
    """The engine's side → ({name: tokens}, {name: prompt length}, {name:
    (the last rows' logits, the experts each token took, the steps it
    took)}), one sequence at a time.  ``ready(name, tokens, prompt
    length, result)`` is called as each sequence ends."""
    sample = config["reference"]["sample"]
    k, n_shared = int(sample["decode_tokens"]), \
        int(sample["shared_prefix_tokens"])
    named, prompts = sequences(config, eng, seqs, n_prompt, seed)
    block, budget = eng.icfg.kv_block_size, eng.icfg.token_budget
    if n_shared % block or not budget < n_shared < prompts["chunked"]:
        raise SystemExit("shared_prefix_tokens is not whole blocks inside "
                         "the long prompt and over a step")
    system = {}
    eng.state.reset_prefix_cache()
    for name, tokens in named.items():
        t0 = time.monotonic()
        if name == "again_sample0":
            # a cold run in what the others left: nothing to alias
            eng.state.reset_prefix_cache()
        left_slots_first(eng)
        cached = eng.timings["cached_tokens"]
        got, took, steps = paged_logits(eng, step, tokens, prompts[name],
                                        k + 1)
        cached = eng.timings["cached_tokens"] - cached
        if name == "shared":
            if cached != n_shared:
                raise SystemExit(
                    f"the second sequence aliased {cached} tokens of the "
                    f"{n_shared} the long prompt left indexed")
            # the aliased rows were computed, and routed, in that run
            took = np.concatenate(
                [system["chunked"][1][:, :n_shared], took], axis=1)
        elif cached and not name.startswith("sample"):
            raise SystemExit(f"{name} aliased {cached} tokens; it is to "
                             "be computed whole")
        system[name] = (got, took, steps)
        if times is not None:
            times["system." + name] = time.monotonic() - t0
        if ready is not None:
            ready(name, tokens, prompts[name], system[name])
    eng.state.reset_prefix_cache()
    if system["chunked"][2] < -(-prompts["chunked"] // budget) + k \
            or system["shared"][2] != 1 + k:
        raise SystemExit("the long prompt was not prefilled over several of "
                         "the engine's steps, or the second sequence not in "
                         "one")
    return named, prompts, system


def summary(read: dict, prompts: dict, wrong=None) -> dict:
    """The comparisons' values from the sequences' readings."""
    note("reference_latent_groups", read=read, wrong=wrong,
         long_prompt=prompts["chunked"])
    first = [n for n in read if n.startswith("sample")]
    return {
        "followed_prefill": max(read[n]["prefill"] for n in first),
        "followed_decode": max(read[n]["decode"] for n in first),
        "chunked_prefill": read["chunked"]["prefill"],
        "chunked_decode": read["chunked"]["decode"],
        "shared_prefix_prefill": read["shared"]["prefill"],
        "shared_prefix_decode": read["shared"]["decode"],
        "reused_slots_prefill": read["again_sample0"]["prefill"],
        "reused_slots_decode": read["again_sample0"]["decode"],
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
                    for name in named}, prompts, wrong)


def group_checks(eng, step, behind, config: dict, seqs: dict,
                 n_prompt: dict, seed: int, times: dict):
    """The engine's side of the comparisons above, now; → a function that
    waits for the reference's side and gives the ``compared`` entries."""
    refspec = config["reference"]
    tol = refspec["tolerance"]
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_latent_groups")
    jobs = {}
    _, prompts, _ = system_side(
        eng, step, config, seqs, n_prompt, seed, times,
        ready=lambda name, tokens, n, result: jobs.update({
            name: behind.submit(one_reading, ref, eng.model.params, config,
                                name, tokens, n, result,
                                eng.icfg.token_budget, None, times)}))

    def finish() -> dict:
        t0 = time.monotonic()
        read = {name: job.result() for name, job in jobs.items()}
        times["reference.waited_for"] = time.monotonic() - t0
        return {name: {"system": value, "reference": 0.0, "rel": value,
                       "tol": limit, "ok": bool(value <= limit)}
                for name, value in summary(read, prompts).items()
                for limit in (tol["routing_short"
                                  if name == "routing_shortfall"
                                  else "followed_rel"],)}

    return finish


def prefill_documents(eng, mix: dict, seed: int) -> int:
    """The mix's hot documents through the engine's ordinary steps, one
    token answered each, flushed and LEFT INDEXED → the blocks they left
    cached.  The ids are the load generator's own
    (``traffic.Requests.prefixes``)."""
    from deepspeed_tpu.inference import SamplingParams
    docs = T.Requests(mix, seed, eng.cfg.vocab_size).prefixes
    eng.generate({600000 + i: list(doc) for i, doc in enumerate(docs)},
                 SamplingParams(temperature=0.0, max_new_tokens=1))
    return eng.state.allocator.cached_free_blocks


def memory_now() -> dict:
    """The device's bytes in use and their peak so far."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def step_parts(ctx, rec):
    """``shareddocs_step_parts`` of a traced run, from the trace reduced
    as ``run.py`` will reduce it for the readers."""
    from benchmarks.lib import arith_dsv2, trace as tracelib
    view = {**rec, "config": ctx["config"], "trace": tracelib.reduce_dir(
        rec.get("trace_dir"), aliases=ctx["config"].get("trace_groups"))}
    if not ctx["rehearse"]:
        from benchmarks.lib.peaks import peaks_for
        view["peaks"] = peaks_for(ctx["devices"][0].device_kind)
    note("shareddocs_step_parts", **arith_dsv2.parts(view))


def run(ctx):
    import deepspeed_tpu.gateway as gateway
    config, mix = ctx["config"], ctx["traffic"]
    preset_config(config)
    checks, times, waiting = {}, {}, []
    engine_logits, spawn_gateway = serve.engine_logits, gateway.spawn_gateway
    behind = ThreadPoolExecutor(1)        # the reference's one thread

    memory, engines = {}, []

    def and_groups(eng, seqs, n_prompt, mbs):
        t0 = time.monotonic()
        engines.append(eng)
        memory["engine_built"] = memory_now()
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
        waiting.append(group_checks(eng, step, behind, config, seqs,
                                    n_prompt, ctx["args"].seed, times))
        return got

    def and_documents(eng, gcfg):
        # the engine is warm and no gateway drives it yet
        for finish in waiting:
            checks.update(finish())
        memory["compared"] = memory_now()
        t0 = time.monotonic()
        left = prefill_documents(eng, mix, ctx["args"].seed)
        times["documents"] = time.monotonic() - t0
        note("latent_groups_times", documents_blocks=left, memory=memory,
             seconds={k: round(v, 2) for k, v in times.items()})
        return spawn_gateway(eng, gcfg)

    try:
        with mock.patch.object(serve, "engine_logits", and_groups), \
                mock.patch.object(gateway, "spawn_gateway", and_documents):
            rec = serve.run(ctx)
    finally:
        behind.shutdown()
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    for eng in engines:
        # over the whole run, warm-up and drain included
        snap = eng.metrics.snapshot()
        note("shareddocs_pool", prefix_evictions=eng.state.prefix_evictions,
             preemptions=snap.get("serving_preemptions_total"),
             cached_free_blocks=eng.state.allocator.cached_free_blocks,
             free_blocks=eng.state.allocator.free_blocks)
    if ctx["args"].trace:
        step_parts(ctx, rec)
    return rec
