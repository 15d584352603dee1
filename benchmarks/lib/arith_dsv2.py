"""Operations and bytes that a serving step of DeepSeek-V2's block
requires: latent attention with a query latent in every layer, a leading
dense layer, then expert layers of a SHARE of the routed experts beside a
shared MLP, from its shapes and from what the program's spans say of the
step.  Beside ``arith_mla.py`` (two sublayers a layer, experts that
compute nothing, no leading layer, no shared MLP), whose span reading
(``stage_spans``) it uses.

A lower bound on what ANY implementation must do.  ``m`` is a
configuration file's published keys with its ``arith`` block laid over
them: ``hidden_size`` (d), ``num_attention_heads`` (H),
``num_hidden_layers``, ``first_k_dense_replace``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``intermediate_size`` (the dense MLP),
``moe_intermediate_size``, ``n_shared_experts``, ``n_routed_experts``
(held here), ``router_outputs``, ``num_experts_per_tok``, ``vocab_size``
(the slice held).

A step is its ``ds.serve.stage`` span (``n_tokens``, ``n_seqs``,
``latent_tokens``: the cached rows ONE latent layer reads, each scheduled
sequence's once; ``latent_tokens_one``: those of its one-token runs;
``latent_pairs``: the (query, cached row) pairs one layer's causal
attention holds; ``n_tiles_one``: its one-token runs; ``cached_tokens``:
prompt rows its admissions were spared by aliasing indexed blocks;
``prefix_evictions``: indexed blocks reclaimed since the step before) and,
by ``sid``, its ``ds.serve.readback`` span (``moe_assignments``: computed
here, ``moe_assignments_made``, ``moe_experts_touched``,
``moe_groups_open_here``: of its rows, a row a layer, those that opened a
device group held here).

The kernel's two calls are counted apart, because they are bound by
different things at 128 heads.  The ONE-TOKEN call reads every cached row
of its sequences once (576 values of 2 bytes) and can only be computed in
the folded form (a query over the cached row itself: 2 x H x (576 + 512)
operations a row, 242 a byte where the chip's ridge is 240): its least
time is the larger of the two.  The RUN call (a prompt's chunk) is counted
in the least either form needs, as ``arith_mla.py`` counts it: every
head's score over a key's 192 and weighted value's 128 a pair, and the
cached rows of its sequences once.  Each layer's five projections
(``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``), the dense MLP, the
routers, the shared MLPs: two operations a weight a token, the weights
once a step.  An expert: three products an assignment COMPUTED HERE, its
weights once where it took a row.  Once a step the head over the rows
that sample.  Not counted: norms, activations, rotary, softmax, the sort
and gathers of the routing.
"""

import re

from benchmarks.lib import arith, arith_mla, program_spans, trace

STEP_KEYS = ("n_tokens", "n_seqs", "latent_tokens", "latent_tokens_one",
             "latent_pairs", "n_tiles_one", "cached_tokens",
             "prefix_evictions")
MOE_KEYS = ("moe_assignments", "moe_assignments_made", "moe_experts_touched",
            "moe_groups_open_here")
KERNEL = re.compile(r"^latent_attention_h(\d+)")


def model(config: dict) -> dict:
    return {**config, **config.get("arith", {})}


def latent_row(m) -> int:
    """Values a token leaves in one layer's pool."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def mla_params(m) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    rank, q_rank = m["kv_lora_rank"], m["q_lora_rank"]
    return (d * q_rank + q_rank * h * qk + d * latent_row(m)
            + rank * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_layers(m) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_params(m) -> int:
    """The weights every step reads whole: the attentions, the leading
    dense MLP, the shared MLPs and the routers."""
    d = m["hidden_size"]
    return (m["num_hidden_layers"] * mla_params(m)
            + m["first_k_dense_replace"] * 3 * d * m["intermediate_size"]
            + expert_layers(m) * (m["n_shared_experts"] * expert_params(m)
                                  + d * m["router_outputs"]))


def one_token_flops(m, s) -> float:
    """The folded form: every head's query over a cached row's 576 for
    the score and 512 for the value."""
    return 2.0 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * (latent_row(m) + m["kv_lora_rank"]) * s["latent_tokens_one"]


def one_token_bytes(m, s, cache_bytes: int = 2) -> float:
    return m["num_hidden_layers"] * cache_bytes * latent_row(m) \
        * s["latent_tokens_one"]


def run_flops(m, s) -> float:
    """The least either form needs: a key's 192 and a value's 128 a
    (query, cached row) pair a head."""
    pairs = s["latent_pairs"] - s["latent_tokens_one"]
    per_pair = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] \
        + m["v_head_dim"]
    return 2.0 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * per_pair * pairs


def run_bytes(m, s, cache_bytes: int = 2) -> float:
    return m["num_hidden_layers"] * cache_bytes * latent_row(m) \
        * (s["latent_tokens"] - s["latent_tokens_one"])


def expert_gemm_flops(m, s) -> float:
    return 2.0 * s["moe_assignments"] * expert_params(m)


def expert_gemm_bytes(m, s, weight_bytes: int = 2) -> float:
    per_row = 3 * (m["hidden_size"] + m["moe_intermediate_size"])
    return weight_bytes * (s["moe_experts_touched"] * expert_params(m)
                           + s["moe_assignments"] * per_row)


def step_flops(m, s) -> float:
    return (2.0 * s["n_tokens"] * fixed_params(m)
            + one_token_flops(m, s) + run_flops(m, s)
            + expert_gemm_flops(m, s)
            + 2.0 * s["n_seqs"] * m["hidden_size"] * m["vocab_size"])


def step_bytes(m, s, weight_bytes: int = 2) -> float:
    d = m["hidden_size"]
    return ((fixed_params(m) + d * m["vocab_size"]) * weight_bytes
            + one_token_bytes(m, s) + run_bytes(m, s)
            + m["num_hidden_layers"] * 2 * latent_row(m) * s["n_tokens"]
            + expert_gemm_bytes(m, s)
            + s["n_tokens"] * d * weight_bytes)


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last, each with what its readback span (same ``sid``) says of the
    experts.  Empty where the trace has no such spans or they lack these
    counts (a program that has no such model, as the parent's)."""
    if "_dsv2_steps" not in rec:
        staged, spans = arith_mla.stage_spans(rec)
        moe = {st.get("sid"): st for _, _, nm, st in spans
               if nm == arith_mla.READBACK and all(k in st for k in MOE_KEYS)}
        steps = []
        for _, st in staged[:-1]:
            back = moe.get(st.get("sid"))
            if back is not None and all(k in st for k in STEP_KEYS):
                steps.append({**{k: float(st[k]) for k in STEP_KEYS},
                              **{k: float(back[k]) for k in MOE_KEYS}})
        rec["_dsv2_steps"] = steps
    return rec["_dsv2_steps"]


def kernel_seconds(rec) -> dict:
    """Device 0's seconds inside the traced window in the latent kernel's
    calls, by the tile height in the call's name
    (``latent_attention_h<height>``) → {height: seconds}."""
    out = {}
    path = arith_mla._xplane(rec)
    window = (rec.get("trace") or {}).get("window")
    if path and window:
        _, ops, _ = program_spans.read(path)
        for s, e, text in trace.clip(ops, window):
            found = KERNEL.match(trace.parse_instruction(text)[0])
            if found:
                h = int(found.group(1))
                out[h] = out.get(h, 0.0) + (e - s)
    return out


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of ``per_step(m, step, peaks) ->
    seconds``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec:
        return None
    m = model(rec["config"])
    return sum(per_step(m, s, rec["peaks"]) for s in steps)


def _roof(flops_of, bytes_of):
    return lambda m, s, peaks: arith.roofline_seconds(
        flops_of(m, s), bytes_of(m, s), peaks)[0]


def parts(rec) -> dict:
    """The cell's own readings, as it would report them if
    ``BENCHMARK.json`` had room for their entries: each roofline share a
    part's least time over its device time in the traced window, in
    percent; {} where the trace has none of it."""
    t = rec.get("trace") or {}
    steps = traced_steps(rec)
    if not steps or not t.get("busy_s"):
        return {}
    kernel = kernel_seconds(rec)
    # a one-token run's tile is one row high; a longer run's the other
    run_s = sum(v for h, v in kernel.items() if h != 1)

    def share(kernel_s, per_step):
        least = kernel_s and least_seconds(rec, per_step)
        return least and 100.0 * least / kernel_s

    whole = least_seconds(rec, _roof(step_flops, step_bytes))
    delta = rec.get("engine_delta") or {}
    rows = sum(s["n_tokens"] for s in steps) \
        * expert_layers(model(rec["config"]))
    made = sum(s["moe_assignments_made"] for s in steps)
    lo, hi = t["window"]
    return {
        "steps": len(steps), "busy_s": t["busy_s"],
        "kernel_seconds": {f"h{h}": v for h, v in sorted(kernel.items())},
        "chunk_steps": sum(1 for s in steps
                           if s["n_tokens"] > s["n_tiles_one"]),
        "step_roofline": whole and 100.0 * whole / t["busy_s"],
        "latent_one_roofline": share(
            kernel.get(1), _roof(one_token_flops, one_token_bytes)),
        "latent_run_roofline": share(run_s, _roof(run_flops, run_bytes)),
        # the whole window's admissions, from the engine's own counters
        "prefix_hit_token_share": delta.get("prompt_tokens") and 100.0
        * delta["cached_tokens"] / delta["prompt_tokens"],
        "prefix_evictions_per_s": sum(s["prefix_evictions"] for s in steps)
        / (hi - lo),
        "groups_open_here_share": rows and 100.0 * sum(
            s["moe_groups_open_here"] for s in steps) / rows,
        "held_assignment_share": made and 100.0 * sum(
            s["moe_assignments"] for s in steps) / made}
