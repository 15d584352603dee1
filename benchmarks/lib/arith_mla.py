"""Operations and bytes that a serving step of a model with latent
attention (MLA with a query latent) in BOTH sublayers of every shortcut-
connected layer, a dense MLP in each and a SHARE of each layer's experts
behind a router that also takes experts which compute nothing requires,
from its shapes and from what the program's spans say of the step.
Beside ``arith_kda.py``, whose steps carry a recurrent state's counts.

A lower bound on what ANY implementation must do.  ``m`` is a
configuration file's published keys with its ``arith`` block laid over
them: ``hidden_size`` (d), ``num_attention_heads`` (H), ``num_layers``
(layers of two sublayers), ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``ffn_hidden_size`` (a dense MLP, two a layer), ``expert_ffn_hidden_size``,
``n_routed_experts`` (held here), ``router_outputs``, ``vocab_size`` (the
slice held).

A step is its ``ds.serve.stage`` span (``n_tokens``, ``n_seqs``,
``latent_tokens``: the cached rows ONE latent sublayer reads, each
scheduled sequence's once; ``latent_pairs``: the (query, cached row)
pairs one sublayer's causal attention holds, ``n * seen + n (n + 1) / 2``
a run of n rows; ``preemptions``) and, by ``sid``, its
``ds.serve.readback`` span (``moe_assignments``: computed here,
``moe_assignments_made``, ``moe_zero_assignments``, ``moe_experts_touched``:
summed over layers).

Counted, a latent sublayer: ``latent_tokens`` rows of ``kv_lora_rank +
qk_rope_head_dim`` read once a sequence and the step's rows written;
every head's products over ``latent_pairs``: the score over the 192 of a
key and the value's 128, the LEAST either form of the attention needs (the
folded form the program runs multiplies over a cached row's 576 and 512,
139k operations a pair at 64 heads where this counts 41k; what expanding
the keys and values would cost the other form is not counted either);
its five projections (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``:
``W_kvb`` is a token's product in either form).  A dense MLP: three
products.  An expert layer: the router over all its outputs, three
products an assignment COMPUTED HERE, an expert's weights once where it
took a row.  Once a step the head over the rows that sample.  Not
counted: norms, activations, rotary, softmax, the sort and gathers of the
routing, the identity part of the zero-compute experts (an addition a
row).
"""

from benchmarks.lib import arith, program_spans, trace
from benchmarks.lib.arith_kda import _xplane

STAGE, READBACK = "ds.serve.stage", "ds.serve.readback"
STEP_KEYS = ("n_tokens", "n_seqs", "latent_tokens", "latent_pairs")
MOE_KEYS = ("moe_assignments", "moe_assignments_made", "moe_experts_touched",
            "moe_zero_assignments")


def model(config: dict) -> dict:
    return {**config, **config.get("arith", {})}


def n_sublayers(m) -> int:
    return 2 * m["num_layers"]


def latent_row(m) -> int:
    """Values a token leaves in one sublayer's pool."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def mla_params(m) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    rank, q_rank = m["kv_lora_rank"], m["q_lora_rank"]
    return (d * q_rank + q_rank * h * qk + d * latent_row(m)
            + rank * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["ffn_hidden_size"]


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["expert_ffn_hidden_size"]


def fixed_params(m) -> int:
    """The weights every step reads whole: the attentions, the dense
    MLPs and the routers."""
    return (n_sublayers(m) * (mla_params(m) + dense_mlp_params(m))
            + m["num_layers"] * m["hidden_size"] * m["router_outputs"])


def latent_flops(m, pairs: float) -> float:
    """Every head's score over a key's 192 and weighted value's 128, a
    (query, cached row) pair, all sublayers."""
    per_pair = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] \
        + m["v_head_dim"]
    return 2.0 * n_sublayers(m) * m["num_attention_heads"] * pairs * per_pair


def latent_bytes(m, latent_tokens: float, n_tokens: float,
                 cache_bytes: int = 2) -> float:
    return n_sublayers(m) * cache_bytes * latent_row(m) \
        * (latent_tokens + n_tokens)


def expert_gemm_flops(m, s) -> float:
    """The held experts' three products, the assignments computed here."""
    return 2.0 * s["moe_assignments"] * expert_params(m)


def expert_gemm_bytes(m, s, weight_bytes: int = 2) -> float:
    """An expert's weights once where it took a row (summed over the
    layers by the program), each assignment's rows in and out."""
    per_row = 3 * (m["hidden_size"] + m["expert_ffn_hidden_size"])
    return weight_bytes * (s["moe_experts_touched"] * expert_params(m)
                           + s["moe_assignments"] * per_row)


def step_flops(m, s) -> float:
    return (2.0 * s["n_tokens"] * fixed_params(m)
            + latent_flops(m, s["latent_pairs"])
            + expert_gemm_flops(m, s)
            + 2.0 * s["n_seqs"] * m["hidden_size"] * m["vocab_size"])


def step_bytes(m, s, weight_bytes: int = 2) -> float:
    d = m["hidden_size"]
    return ((fixed_params(m) + d * m["vocab_size"]) * weight_bytes
            + latent_bytes(m, s["latent_tokens"], s["n_tokens"])
            + expert_gemm_bytes(m, s)
            + s["n_tokens"] * d * weight_bytes)


def stage_spans(rec) -> list:
    """The ``ds.serve.stage`` spans staged wholly inside the traced
    window → [(start, stats)], and every program span of the trace."""
    if "_mla_spans" not in rec:
        staged, spans = [], []
        path = _xplane(rec)
        window = (rec.get("trace") or {}).get("window")
        if path and window:
            lo, hi = window
            threads, _, _ = program_spans.read(path)
            spans = [(s, e, nm, st) for line in threads.values()
                     for s, e, nm, st in line]
            staged = sorted((s, st) for s, e, nm, st in spans
                            if nm == STAGE and lo <= s and e <= hi)
        rec["_mla_spans"] = (staged, spans)
    return rec["_mla_spans"]


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last, each with what its readback span (same ``sid``) says of the
    experts.  Empty where the trace has no such spans or they lack these
    counts (a program that has no such model, as the parent's)."""
    if "_mla_steps" not in rec:
        staged, spans = stage_spans(rec)
        moe = {st.get("sid"): st for _, _, nm, st in spans
               if nm == READBACK and all(k in st for k in MOE_KEYS)}
        steps = []
        for _, st in staged[:-1]:
            back = moe.get(st.get("sid"))
            if back is not None and all(k in st for k in STEP_KEYS):
                steps.append({**{k: float(st[k]) for k in STEP_KEYS},
                              **{k: float(back[k]) for k in MOE_KEYS}})
        rec["_mla_steps"] = steps
    return rec["_mla_steps"]


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of the roofline time of
    ``per_step(m, step) -> (flops, bytes)`` → ``(steps, least seconds,
    how many steps each bound decides)``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec \
            or "q_lora_rank" not in rec["config"]:
        return None
    m = model(rec["config"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        sec, which = arith.roofline_seconds(*per_step(m, s), rec["peaks"])
        least += sec
        bounds[which] += 1
    return len(steps), least, bounds
