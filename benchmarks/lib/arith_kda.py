"""Operations and bytes that a serving step of a model with delta-rule
linear attention (KDA) in most layers, latent attention (MLA) in the
rest and a SHARE of each expert layer's experts requires, from its
shapes and from what the program's spans say of the step.  Beside
``arith.py`` and ``arith_moe.py``, which know one kind of layer and
count every expert of every layer: here a layer holds ONE kind of cache,
and an expert's weights are read only where the expert is held AND took
a row.

A lower bound on what ANY implementation must do.  ``m`` is a
configuration file's published keys with its ``arith`` block laid over
them: ``hidden_size`` (d), ``num_attention_heads`` (H), ``head_dim``
(KDA's key and value size), ``short_conv_kernel_size``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size`` (the dense MLP), ``moe_intermediate_size``,
``num_experts`` (held here), ``router_outputs``, ``num_experts_per_tok``,
``first_k_dense_replace``, ``layer_types``, ``vocab_size`` (the slice
held), ``state_bytes`` (the stored type of a state).

A step is its ``ds.serve.stage`` span (``n_tokens``, ``n_seqs``,
``latent_tokens``: the cached rows its latent layer reads, ``state_rows``,
``scan_tokens``, ``state_starts``, ``state_replays``) and, by ``sid``,
its ``ds.serve.readback`` span (``moe_assignments``: computed here,
``moe_assignments_made``, ``moe_experts_touched``: summed over layers).

Counted, a KDA layer: a one-token row reads and writes its state in the
stored type and the convolution's tail (width - 1 inputs, as the
activations are stored), and q, k, v, g, beta in and o out; a scanned token costs the chunked form's least
products (the causal half of k k^T and q k^T over a chunk, each with its
product into the values; k^T u into the chunk's state; q and k times the
state coming in) and its inputs and output, a longer run writes its last
state and, where it does not start at position 0, reads its first; the
six projections.  A latent layer: ``latent_tokens`` rows of ``kv_lora_rank
+ qk_rope_head_dim`` read and the new rows written, the folded form's
products (H heads over a row of 576 for the scores and of 512 for the
values), its five projections.  An expert layer: the router over all its
outputs, the shared expert, three products an assignment COMPUTED HERE,
an expert's weights once where it took a row.  Once a step the head
over the rows that sample.  Not counted: norms, activations, rotary,
softmax, the convolution's products, the decays' exponentials, the
sort and gathers of the routing.
"""

from benchmarks.lib import arith, program_spans, trace

STAGE, READBACK = "ds.serve.stage", "ds.serve.readback"
STEP_KEYS = ("n_tokens", "n_seqs", "latent_tokens", "state_rows",
             "scan_tokens", "state_starts", "state_replays")
MOE_KEYS = ("moe_assignments", "moe_assignments_made", "moe_experts_touched")
KDA_SCOPES = ("kda_conv", "kda_update", "kda_chunk", "kda_gate")
SCOPES = KDA_SCOPES + ("latent_attn", "latent_write")
CHUNK = 64


def model(config: dict) -> dict:
    return {**config, **config.get("arith", {})}


def kinds(m) -> list:
    return list(m["layer_types"][:m["num_hidden_layers"]])


def n_kda(m) -> int:
    return kinds(m).count("kda")


def n_latent(m) -> int:
    return kinds(m).count("mla")


def n_expert_layers(m) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def kda_width(m) -> int:
    """H * K (= H * V): a head's key and value size is ``head_dim``."""
    return m["num_attention_heads"] * m["head_dim"]


def kda_params(m) -> int:
    """A KDA mixer's projections: q, k, v, the decay, the gate, the
    output, and beta."""
    d = m["hidden_size"]
    return 6 * d * kda_width(m) + d * m["num_attention_heads"]


def mla_params(m) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    rank = m["kv_lora_rank"]
    return (d * h * qk + d * (rank + m["qk_rope_head_dim"])
            + rank * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d + d * h)


def latent_row(m) -> int:
    """Values a token leaves in the latent pool."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def state_elements(m) -> int:
    """One sequence's state in one KDA layer: H x K x V."""
    return kda_width(m) * m["head_dim"]


def tail_elements(m) -> int:
    """The raw inputs the convolution has to carry: width - 1, over the
    q, k and v channels."""
    return (m["short_conv_kernel_size"] - 1) * 3 * kda_width(m)


def row_io_elements(m) -> int:
    """q, k, v, g, beta in and o out of the recurrence, one token."""
    return 5 * kda_width(m) + m["num_attention_heads"]


def state_bytes_per_seq(m, act_bytes: int = 2) -> int:
    """A sequence's state (``state_bytes`` an element) and the tail AS
    STORED (all ``width`` raw inputs in the activations' type: one more
    than the convolution needs, for a row fed again) over the KDA
    layers."""
    return n_kda(m) * (
        m["state_bytes"] * state_elements(m)
        + act_bytes * m["short_conv_kernel_size"] * 3 * kda_width(m))


def carried_bytes(m, act_bytes: int = 2) -> int:
    """What one layer has to read (or write) of a sequence to go on
    where it stopped: the state and the tail's needed inputs."""
    return m["state_bytes"] * state_elements(m) \
        + act_bytes * tail_elements(m)


def update_bytes(m, rows: float, act_bytes: int = 2) -> float:
    """The one-token update of ``rows`` sequences, all KDA layers."""
    per_row = 2 * carried_bytes(m, act_bytes) \
        + act_bytes * row_io_elements(m)
    return n_kda(m) * rows * per_row


def update_flops(m, rows: float) -> float:
    """Decay the state, predict with k, add the outer product, read with
    q: seven a state element."""
    return 7.0 * n_kda(m) * rows * state_elements(m)


def chunk_flops(m, tokens: float) -> float:
    """The chunked form's least products for ``tokens``, all KDA layers."""
    q = (CHUNK + 1) / 2.0
    # a head: the causal halves of k k^T and q k^T (2 q K each) and
    # their products into the values (2 q V each); k^T u into the state,
    # q and k times the state coming in (2 K V each)
    per_token = 8.0 * q * kda_width(m) + 6.0 * state_elements(m)
    return n_kda(m) * tokens * per_token


def chunk_bytes(m, tokens: float, runs: float, starts: float,
                act_bytes: int = 2) -> float:
    continuing = max(0.0, runs - starts)
    return n_kda(m) * (
        tokens * act_bytes * row_io_elements(m)
        + (runs + continuing) * carried_bytes(m, act_bytes))


def scan_runs(s) -> float:
    """The runs of several tokens a step holds."""
    return max(0.0, s["n_seqs"] - s["state_rows"] - s["state_replays"])


def latent_flops(m, latent_tokens: float) -> float:
    """The folded form: every head's query over a cached row for the
    score, and the weighted rows for the value."""
    return 2.0 * n_latent(m) * m["num_attention_heads"] * latent_tokens \
        * (latent_row(m) + m["kv_lora_rank"])


def latent_bytes(m, latent_tokens: float, n_tokens: float,
                 cache_bytes: int = 2) -> float:
    return n_latent(m) * cache_bytes * latent_row(m) \
        * (latent_tokens + n_tokens)


def expert_gemm_flops(m, s) -> float:
    """The held experts' three products, the assignments computed here."""
    return 2.0 * s["moe_assignments"] * expert_params(m)


def expert_gemm_bytes(m, s, weight_bytes: int = 2) -> float:
    """An expert's weights once where it took a row (summed over the
    layers by the program), each assignment's rows in and out."""
    per_row = 3 * (m["hidden_size"] + m["moe_intermediate_size"])
    return weight_bytes * (s["moe_experts_touched"] * expert_params(m)
                           + s["moe_assignments"] * per_row)


def fixed_params(m) -> int:
    """The weights every step reads whole: the mixers, the dense MLPs,
    the routers and the shared experts."""
    d = m["hidden_size"]
    return (n_kda(m) * kda_params(m) + n_latent(m) * mla_params(m)
            + m["first_k_dense_replace"] * dense_mlp_params(m)
            + n_expert_layers(m) * (d * m["router_outputs"]
                                    + expert_params(m)))


def step_flops(m, s) -> float:
    return (2.0 * s["n_tokens"] * fixed_params(m)
            + update_flops(m, s["state_rows"])
            + chunk_flops(m, s["scan_tokens"])
            + latent_flops(m, s["latent_tokens"])
            + expert_gemm_flops(m, s)
            + 2.0 * s["n_seqs"] * m["hidden_size"] * m["vocab_size"])


def step_bytes(m, s, weight_bytes: int = 2) -> float:
    d = m["hidden_size"]
    return ((fixed_params(m) + d * m["vocab_size"]) * weight_bytes
            + update_bytes(m, s["state_rows"])
            + chunk_bytes(m, s["scan_tokens"], scan_runs(s),
                          min(s["state_starts"], scan_runs(s)))
            + latent_bytes(m, s["latent_tokens"], s["n_tokens"])
            + expert_gemm_bytes(m, s)
            + s["n_tokens"] * d * weight_bytes)


def _xplane(rec):
    return trace.find_xplane(rec["trace_dir"]) \
        if rec.get("kind") == "serve" and rec.get("trace_dir") else None


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last, each with what its readback span (same ``sid``) says of the
    experts.  Empty where the trace has no such spans or they lack these
    counts (a program that has no such model, as the parent's)."""
    if "_kda_steps" not in rec:
        steps = []
        path = _xplane(rec)
        window = (rec.get("trace") or {}).get("window")
        if path and window:
            lo, hi = window
            threads, _, _ = program_spans.read(path)
            spans = [(s, e, nm, st) for line in threads.values()
                     for s, e, nm, st in line]
            moe = {st.get("sid"): st for _, _, nm, st in spans
                   if nm == READBACK and all(k in st for k in MOE_KEYS)}
            staged = sorted((s, st) for s, e, nm, st in spans
                            if nm == STAGE and lo <= s and e <= hi)
            for _, st in staged[:-1]:
                back = moe.get(st.get("sid"))
                if back is not None and all(k in st for k in STEP_KEYS):
                    steps.append({**{k: float(st[k]) for k in STEP_KEYS},
                                  **{k: float(back[k]) for k in MOE_KEYS}})
        rec["_kda_steps"] = steps
    return rec["_kda_steps"]


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of the roofline time of
    ``per_step(m, step) -> (flops, bytes)`` → ``(steps, least seconds,
    how many steps each bound decides)``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec \
            or "kv_lora_rank" not in rec["config"]:
        return None
    m = model(rec["config"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        sec, which = arith.roofline_seconds(*per_step(m, s), rec["peaks"])
        least += sec
        bounds[which] += 1
    return len(steps), least, bounds


def scope_seconds(rec) -> dict:
    """Device 0's seconds inside the traced window under each of the
    mixers' scopes (an operation counts where one of its JAX paths holds
    the scope as a whole component), and ``busy_s``, the union of all
    its operations there.  {} where the trace holds none of them."""
    if "_kda_scopes" not in rec:
        out = {}
        path = _xplane(rec)
        window = (rec.get("trace") or {}).get("window")
        if path and window:
            _, ops, op_names = program_spans.read(path)
            ops = trace.clip(ops, window)
            memo = {}
            for s, e, text in ops:
                if text not in memo:
                    name, opcode, _ = trace.parse_instruction(text)
                    parts = {c for p in op_names.get(name, ())
                             if not p.startswith("@") for c in p.split("/")}
                    memo[text] = None if opcode in trace.CONTAINERS else \
                        next((sc for sc in SCOPES if sc in parts), "")
                if memo[text]:
                    out[memo[text]] = out.get(memo[text], 0.0) + (e - s)
            if out:
                out["busy_s"] = trace._length(trace._union(
                    [(s, e) for s, e, _ in ops]))
        rec["_kda_scopes"] = out
    return rec["_kda_scopes"]


def scope_roofline(rec, scope: str, per_step):
    """``scope``'s least time over its device time, in percent; None
    where either is missing."""
    kernel_s = scope_seconds(rec).get(scope)
    found = kernel_s and least_seconds(rec, per_step)
    if not found:
        return None
    steps, least, bounds = found
    from benchmarks.lib.common import note
    note(scope + "_roofline", steps=steps, least_s=least, kernel_s=kernel_s,
         bound_by=bounds)
    return 100.0 * least / kernel_s
