"""Driver of a serving mix whose model keeps a Mamba-2 state for every
sequence in most layers, keys and values in the rest, and a SHARE of every
layer's softmax-routed experts: ``serve.py``, whole, and beside its
comparison of logits the comparisons of the drivers that exist, put
together for a model that needs all of them at once.

What it adds to ``serve.py``, and why.  ``serve_routed.py``: with 72 router
outputs and ten chosen by their logits, a token whose tenth and eleventh
logits stand within bfloat16's rounding takes another expert in the system
than in a float32 reference, which moves a tenth of that layer's routed
output; so the engine says which experts each token took, the reference
FOLLOWS that choice (``following`` in the reference's file) and everything
else is its own, and how far a taken expert's logit falls short of the
reference's own tenth has a limit of its own, which a choice made wrongly
fails.  ``serve_recurrent.py``: a state is advanced and not appended to, so
a prompt cut over several steps has to carry its state and the
convolution's tail across each cut and each chunk, and a slot that a
sequence left must not hand its state on (``left_slots_first``).  So, when
``serve.py`` has made its comparison and the timed loop has not begun, one
sequence at a time through the logits-returning step that also says the
experts each token took (``serve_hybrid_share.py``'s ``routing_step`` and
``paged_logits``, built once and compiled at both row counts side by
side), all against the reference that follows:

* ``followed_*``: ``serve.py``'s own three sequences;
* ``chunked_*``: a seeded prompt of ``reference.sample.long_prompt``
  tokens prefilled in the engine's ordinary steps of ``token_budget``
  tokens (four step boundaries and eight chunk boundaries at 2,100 tokens,
  512 a step and 256 a chunk), then the sample's fed tokens;
* ``reused_slots_*``: the first sample once more, in the slot the others
  left;
* ``routing_shortfall``: the largest shortfall of a taken expert over all
  of these, under its own limit.

It also checks the configuration's keys that ``benchmarks/lib/weights.py``
``transformer_config`` does not know (``CHECKED``: every ``mamba_*`` key,
the four multipliers, the layer kinds, the router and the share), says on
standard output how the engine divided the device's memory between the
state rows and the block pool (the program's own gauges), and in a traced
run prints ``ragmoe_step_parts``: the step's roofline shares by
``benchmarks/lib/arith_ragmoe.py``, which would be per-layer metrics of
their own if ``BENCHMARK.json`` had room for their entries.

``serve.py`` gives no seam for a second comparison: ``run`` is entered with
``engine_logits`` wrapped, for the one call it makes of it, as the other
drivers do.

A run has 360 s in the driver's check, set-up, warm-up and window, and a
first run has no compiled program.  So a sequence's reference runs on a
thread of its own behind the next sequences' steps and behind ``serve.py``'s
warm-up of the engine (where the serving step compiles and the device
waits), and is waited for where ``serve.py`` instruments the engine,
before the load begins; the reference's program is the same whether it
chooses or follows, so what ``serve.py``'s own comparison compiled is found
again.  ``state_share_times`` says what each part took.  (Compiling the
serving step on threads of its own beside the logits-returning step, for
the compile cache to hand to the engine, was tried and is gone: four
compiles side by side took the 13 cores 121 s where two take 66, and the
engine's own build found nothing in the cache: my chip run, PR 52.)
"""

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
from benchmarks.lib.drivers.serve_recurrent import left_slots_first

KINDS = {"mamba": "mamba", "full": "attention"}
# configuration key -> what the preset has to run for it
CHECKED = {
    "mamba_n_heads": lambda c: c.ssm_heads,
    "mamba_d_head": lambda c: c.ssm_head_dim,
    "mamba_n_groups": lambda c: c.ssm_groups,
    "mamba_d_state": lambda c: c.ssm_state,
    "mamba_d_conv": lambda c: c.ssm_conv,
    "mamba_chunk_size": lambda c: c.ssm_chunk,
    "mamba_expand": lambda c: c.ssm_d // c.d_model,
    "embedding_multiplier": lambda c: c.embed_scale,
    "logits_scaling": lambda c: 1.0 / c.head_scale,
    "attention_multiplier": lambda c: c.attn_scale,
    "residual_multiplier": lambda c: c.residual_scale,
    "position_embedding_type": lambda c: {"none": "nope"}.get(c.position),
    "normalization_function": lambda c: c.norm,
    "attention_bias": lambda c: c.attn_bias,
    "shared_intermediate_size": lambda c: c.moe_shared_ff,
    "num_local_experts": lambda c: c.experts_here,
    "router_outputs": lambda c: c.num_experts,
    "experts_held": lambda c: list(c.experts_held or (0, c.num_experts)),
    "num_experts_per_tok": lambda c: c.moe_top_k,
    "layer_types": lambda c: [KINDS.get(k) for k in c.layer_kinds],
}


def check_config(config: dict, cfg):
    if set(cfg.mixer_stacks) != {"mamba", "full"} \
            or cfg.experts_held is None or cfg.moe_d_ff != cfg.d_ff \
            or cfg.moe_score != "softmax" or not cfg.moe_norm_topk \
            or cfg.moe_shared_gate:
        raise SystemExit("the configuration's preset is not one of state "
                         "and attention layers with a share of softmax-"
                         "routed experts beside an ungated shared MLP; "
                         "this driver is for one that is")
    # (the published list of layer kinds is kept whole: the kept layers
    # are its first ``num_hidden_layers``)
    told = {**config, "layer_types":
            config["layer_types"][:config["num_hidden_layers"]]}
    for key, runs in CHECKED.items():
        if key in told and told[key] != runs(cfg):
            raise SystemExit(f"configuration file says {key}={told[key]}, "
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
    out["again_sample0"] = out["sample0"]
    prompts["again_sample0"] = prompts["sample0"]
    return out, prompts


def system_side(eng, step, config: dict, seqs: dict, n_prompt: dict,
                seed: int, times=None, ready=None):
    """The engine's side of the comparisons → ({name: tokens}, {name:
    prompt length}, {name: (the last rows' logits, the experts each token
    took, the steps it took)}), one sequence at a time, each in the slot
    the one before it left.  ``ready(name, tokens, prompt length,
    result)`` is called as each sequence ends."""
    k = int(config["reference"]["sample"]["decode_tokens"])
    named, prompts = sequences(config, eng, seqs, n_prompt, seed)
    system = {}
    for name, tokens in named.items():
        t0 = time.monotonic()
        left_slots_first(eng)
        system[name] = paged_logits(eng, step, tokens, prompts[name], k + 1)
        if times is not None:
            times["system." + name] = time.monotonic() - t0
        if ready is not None:
            ready(name, tokens, prompts[name], system[name])
    budget = eng.icfg.token_budget
    if system["chunked"][2] < -(-prompts["chunked"] // budget) + k \
            or prompts["chunked"] <= 2 * budget:
        raise SystemExit("the long prompt was not prefilled over several "
                         "of the engine's steps")
    return named, prompts, system


def summary(read: dict, prompts: dict, wrong=None) -> dict:
    """The comparisons' values from the sequences' readings."""
    note("reference_state_share", read=read, wrong=wrong,
         long_prompt=prompts["chunked"])

    def worst(names, phase):
        return max(read[n][phase] for n in names)

    first = [n for n in read if n.startswith("sample")]
    again = [n for n in read if n.startswith("again_")]
    return {
        "followed_prefill": worst(first, "prefill"),
        "followed_decode": worst(first, "decode"),
        "chunked_prefill": read["chunked"]["prefill"],
        "chunked_decode": read["chunked"]["decode"],
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
                    for name in named}, prompts, wrong)


def share_checks(eng, step, behind, config: dict, seqs: dict,
                 n_prompt: dict, seed: int, times: dict):
    """The engine's side of the comparisons above, now; → a function that
    waits for the reference's side and gives the ``compared`` entries."""
    refspec = config["reference"]
    tol = refspec["tolerance"]
    ref = load_module(os.path.join(ROOT, refspec["file"]),
                      "bench_reference_state_share")
    # a sequence's reference runs behind the next sequences' steps, on
    # ``behind``'s one thread, and goes on behind what the caller does
    # next
    jobs = {}
    named, prompts, _ = system_side(
        eng, step, config, seqs, n_prompt, seed, times,
        ready=lambda name, tokens, n, result: jobs.update({
            name: behind.submit(one_reading, ref, eng.model.params,
                                config, name, tokens, n, result,
                                eng.icfg.token_budget, None, times)}))

    def finish() -> dict:
        t0 = time.monotonic()
        read = {name: job.result() for name, job in jobs.items()}
        times["reference.waited_for"] = time.monotonic() - t0
        got = summary(read, prompts)
        return {name: {"system": value, "reference": 0.0, "rel": value,
                       "tol": limit, "ok": bool(value <= limit)}
                for name, value in got.items()
                for limit in (tol["routing_short"
                                  if name == "routing_shortfall"
                                  else "followed_rel"],)}

    return finish


def memory_note(eng):
    """How the engine divided the device's memory, from its own gauges."""
    snap = eng.metrics.snapshot()
    import jax
    note("state_share_memory",
         weights_bytes=sum(a.nbytes for a in jax.tree.leaves(eng.params)),
         state_rows_bytes=snap.get("serving_state_rows_bytes"),
         block_pool_bytes=snap.get("serving_block_pool_bytes"),
         state_layers=eng._recurrent.layers,
         block_layers=eng.state.cfg.num_layers)


def step_parts(ctx, rec):
    """``ragmoe_step_parts`` of a traced run: the shares
    ``arith_ragmoe.parts`` computes, from the trace reduced as ``run.py``
    will reduce it for the readers."""
    from benchmarks.lib import arith_ragmoe, trace as tracelib
    view = {**rec, "config": ctx["config"], "trace": tracelib.reduce_dir(
        rec.get("trace_dir"), aliases=ctx["config"].get("trace_groups"))}
    if not ctx["rehearse"]:
        from benchmarks.lib.peaks import peaks_for
        view["peaks"] = peaks_for(ctx["devices"][0].device_kind)
    note("ragmoe_step_parts", **arith_ragmoe.parts(view))


def run(ctx):
    config = ctx["config"]
    preset_config(config)
    checks, times, waiting = {}, {}, []
    engine_logits, instrument = serve.engine_logits, serve.instrument
    behind = ThreadPoolExecutor(1)        # the reference's one thread

    def and_share(eng, seqs, n_prompt, mbs):
        t0 = time.monotonic()
        memory_note(eng)
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
        waiting.append(share_checks(eng, step, behind, config, seqs,
                                    n_prompt, ctx["args"].seed, times))
        return got

    def and_wait(*a, **kw):
        # the engine is warm and the load has not begun
        for finish in waiting:
            checks.update(finish())
        note("state_share_times", seconds={k: round(v, 2)
                                           for k, v in times.items()})
        return instrument(*a, **kw)

    try:
        with mock.patch.object(serve, "engine_logits", and_share), \
                mock.patch.object(serve, "instrument", and_wait):
            rec = serve.run(ctx)
    finally:
        behind.shutdown()
    rec["compared"].update(checks)
    rec["correct"] = bool(rec["correct"] and checks
                          and all(c["ok"] for c in checks.values()))
    if ctx["args"].trace:
        step_parts(ctx, rec)
    return rec
