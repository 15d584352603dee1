"""Operations and bytes that a serving step of a model requires whose
layers hold ONE mixer each, a Mamba-2 mixer (a state and no blocks) in
most and grouped-query attention (blocks and no state) in the rest, with a
SHARE of every layer's routed experts beside a shared MLP, from its shapes
and from what the program's spans say of the step.  Beside ``arith_ssm.py``,
which assumes a mixer AND an attention AND a dense MLP in every layer, and
``arith_kda.py``, whose layers are of other kinds: the counting of a mixer
is ``arith_ssm.py``'s own, over the layers that hold one.

A lower bound on what ANY implementation must do.  ``m`` is a
configuration file's published keys with its ``arith`` block laid over
them: ``hidden_size`` (d), ``num_attention_heads`` x ``head_dim``,
``num_key_value_heads``, ``mamba_n_heads`` x ``mamba_d_head``,
``mamba_n_groups``, ``mamba_d_state``, ``mamba_d_conv``,
``mamba_chunk_size``, ``intermediate_size`` (an expert's width),
``shared_intermediate_size``, ``router_outputs``, ``num_local_experts``
(held here), ``num_experts_per_tok``, ``layer_types``, ``vocab_size``,
``state_bytes`` (the stored type of a state).

A step is its ``ds.serve.stage`` span (``n_tokens``, ``n_seqs``,
``kv_tokens_full``: the cached tokens ONE attention layer reads,
``state_rows``, ``scan_tokens``, ``state_starts``, ``state_replays``) and,
by ``sid``, its ``ds.serve.readback`` span (``moe_assignments``: computed
here, ``moe_assignments_made``, ``moe_experts_touched``: summed over the
layers).

Counted, a Mamba layer: as ``arith_ssm.py`` counts one (a one-token row
reads and writes its state and the tail and its inputs and output; a
scanned token the chunked form's least products and its inputs and
output, a longer run its last state written and its first read unless it
starts at position 0; the two projections).  An attention layer: the
cached keys and values read once, the new ones written, two products a
(query head, cached token), the four projections.  Every layer: the router
over all its outputs, the shared MLP, three products an assignment
COMPUTED HERE, an expert's weights once where it took a row.  Once a step
the tied head over the rows that sample.  Not counted: norms,
activations, softmax, the convolution's products, the decays'
exponentials, the sort and gathers of the routing.
"""

from benchmarks.lib import arith, arith_mla, arith_ssm

STEP_KEYS = ("n_tokens", "n_seqs", "kv_tokens_full", "state_rows",
             "scan_tokens", "state_starts", "state_replays")
MOE_KEYS = ("moe_assignments", "moe_assignments_made", "moe_experts_touched")
READBACK = arith_mla.READBACK


def model(config: dict) -> dict:
    m = {**config, **config.get("arith", {})}
    kinds = list(m["layer_types"][:m["num_hidden_layers"]])
    m["n_mamba"], m["n_attn"] = kinds.count("mamba"), kinds.count("attention")
    return m


def mixers(m) -> dict:
    """``m`` as ``arith_ssm.py`` reads it, its "all layers" the layers
    that hold a mixer."""
    return {**m, "num_hidden_layers": m["n_mamba"],
            "mamba_d_ssm": m["mamba_n_heads"] * m["mamba_d_head"]}


def attn_params(m) -> int:
    d, h, hkv, hd = arith._dims(m)[:4]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def ffn_fixed_params(m) -> int:
    """What every layer's feed-forward reads whole: router and shared MLP."""
    d = m["hidden_size"]
    return d * m["router_outputs"] + 3 * d * m["shared_intermediate_size"]


def fixed_params(m) -> int:
    """The weights every step reads whole."""
    return (m["n_mamba"] * arith_ssm.mixer_params(mixers(m))
            + m["n_attn"] * attn_params(m)
            + m["num_hidden_layers"] * ffn_fixed_params(m))


def kv_bytes_per_token(m, kv_bytes: int = 2) -> int:
    """A token's keys and values over the layers that hold them."""
    _, _, hkv, hd = arith._dims(m)[:4]
    return 2 * hkv * hd * kv_bytes * m["n_attn"]


def state_bytes_per_seq(m) -> int:
    """A sequence's state and tail AS STORED (all ``mamba_d_conv`` raw
    inputs: one more than the convolution needs, for a row fed again)
    over the layers that hold one."""
    ms = mixers(m)
    return m["n_mamba"] * m["state_bytes"] * (
        arith_ssm.state_elements(ms)
        + m["mamba_d_conv"] * arith_ssm.conv_channels(ms))


def update_flops(m, s) -> float:
    return arith_ssm.update_flops(mixers(m), s["state_rows"])


def update_bytes(m, s) -> float:
    return arith_ssm.update_bytes(mixers(m), s["state_rows"])


def scan_flops(m, s) -> float:
    return arith_ssm.scan_flops(mixers(m), s["scan_tokens"])


def scan_bytes(m, s) -> float:
    runs = arith_ssm.scan_runs(s)
    return arith_ssm.scan_bytes(mixers(m), s["scan_tokens"], runs,
                                min(s["state_starts"], runs))


def expert_gemm_flops(m, s) -> float:
    """The held experts' three products, the assignments computed here."""
    return 2.0 * s["moe_assignments"] * expert_params(m)


def expert_gemm_bytes(m, s, weight_bytes: int = 2) -> float:
    """An expert's weights once where it took a row (summed over the
    layers by the program), each assignment's rows in and out."""
    per_row = 3 * (m["hidden_size"] + m["intermediate_size"])
    return weight_bytes * (s["moe_experts_touched"] * expert_params(m)
                           + s["moe_assignments"] * per_row)


def step_flops(m, s) -> float:
    d, h, _, hd = arith._dims(m)[:4]
    return (2.0 * s["n_tokens"] * fixed_params(m)
            + 4.0 * m["n_attn"] * h * hd * s["kv_tokens_full"]
            + update_flops(m, s) + scan_flops(m, s)
            + expert_gemm_flops(m, s)
            + 2.0 * s["n_seqs"] * d * m["vocab_size"])


def step_bytes(m, s, weight_bytes: int = 2) -> float:
    d = m["hidden_size"]
    return ((fixed_params(m) + d * m["vocab_size"]) * weight_bytes
            + (s["kv_tokens_full"] + s["n_tokens"]) * kv_bytes_per_token(m)
            + update_bytes(m, s) + scan_bytes(m, s)
            + expert_gemm_bytes(m, s)
            + s["n_tokens"] * d * weight_bytes)


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last, each with what its readback span (same ``sid``) says of the
    experts.  Empty where the trace has no such spans or they lack these
    counts (a program that has no such model, as the parent's)."""
    if "_ragmoe_steps" not in rec:
        staged, spans = arith_mla.stage_spans(rec)
        moe = {st.get("sid"): st for _, _, nm, st in spans
               if nm == READBACK and all(k in st for k in MOE_KEYS)}
        steps = []
        for _, st in staged[:-1]:
            back = moe.get(st.get("sid"))
            if back is not None and all(k in st for k in STEP_KEYS):
                steps.append({**{k: float(st[k]) for k in STEP_KEYS},
                              **{k: float(back[k]) for k in MOE_KEYS}})
        rec["_ragmoe_steps"] = steps
    return rec["_ragmoe_steps"]


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of the roofline time of
    ``per_step(m, step) -> (flops, bytes)`` → ``(steps, least seconds,
    how many steps each bound decides)``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec \
            or "shared_intermediate_size" not in rec["config"]:
        return None
    m = model(rec["config"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        sec, which = arith.roofline_seconds(*per_step(m, s), rec["peaks"])
        least += sec
        bounds[which] += 1
    return len(steps), least, bounds


def parts(rec) -> dict:
    """The step's shares as the cell would report them if ``BENCHMARK.json``
    had room for their entries (it holds 128 of 128): each roofline share a
    part's least time over its device time in the traced window, in
    percent, or None where the trace has none of it."""
    t = rec.get("trace") or {}
    steps = traced_steps(rec)
    if not steps or not t.get("busy_s"):
        return {}
    scopes = arith_ssm.scope_seconds(rec)

    def share(kernel_s, per_step):
        found = kernel_s and least_seconds(rec, per_step)
        return found and 100.0 * found[1] / kernel_s

    whole = least_seconds(rec, lambda m, s: (step_flops(m, s),
                                             step_bytes(m, s)))
    made = sum(s["moe_assignments_made"] for s in steps)
    return {
        "steps": len(steps), "busy_s": t["busy_s"],
        "bound_by": whole and whole[2],
        "ragmoe_step_roofline": whole and 100.0 * whole[1] / t["busy_s"],
        "ragmoe_ssm_update_roofline": share(
            scopes.get("ssm_update"),
            lambda m, s: (update_flops(m, s), update_bytes(m, s))),
        "ragmoe_ssm_scan_roofline": share(
            scopes.get("ssm_scan"),
            lambda m, s: (scan_flops(m, s), scan_bytes(m, s))),
        "ragmoe_expert_gemm_roofline": share(
            t["groups_s"].get("moe_expert_gemm"),
            lambda m, s: (expert_gemm_flops(m, s), expert_gemm_bytes(m, s))),
        "ragmoe_ssm_share": scopes.get("busy_s") and 100.0 * sum(
            scopes.get(k, 0.0) for k in ("ssm_update", "ssm_scan",
                                         "ssm_conv")) / scopes["busy_s"],
        "scope_seconds": {k: v for k, v in sorted(scopes.items())},
        "ragmoe_state_rows_per_step":
            sum(s["state_rows"] for s in steps) / len(steps),
        "ragmoe_scan_tokens_per_step":
            sum(s["scan_tokens"] for s in steps) / len(steps),
        "ragmoe_held_assignment_share": made and 100.0 * sum(
            s["moe_assignments"] for s in steps) / made}
