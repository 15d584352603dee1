"""Operations and bytes that a serving step of a model with a LAYER
PATTERN requires, from its shapes: leading dense layers and expert
layers counted apart, window and full attention layers counted apart.
Beside ``arith_moe.py``, which counts ``num_hidden_layers`` expert layers
and one attention kind: on a model with a leading dense layer it would
read the expert kernel at 5/4 of its true share.

A lower bound on what any implementation must do: an expert's weights
are counted only where the expert took a row (the program's own count,
below), not wherever a step holds assignments enough to reach every
expert.  ``m`` is a
configuration file's published keys with its ``arith`` block laid over
them: ``hidden_size`` (d), ``num_attention_heads`` x ``head_dim``
(H * D, which need not be d), ``num_key_value_heads``,
``intermediate_size`` (a dense layer's width), ``moe_intermediate_size``
(an expert's), ``num_experts``, ``num_experts_per_tok``,
``num_shared_experts``, ``num_dense_layers``, ``layer_types``,
``sliding_window``, ``attn_gate``.

A step is what the program's spans say of it (``telemetry/tracer.py``).
``ds.serve.stage``: ``n_tokens``, ``n_seqs``, ``kv_tokens_full`` (the
cached tokens a full layer reads, the sum of seen + n over the step's
sequences) and ``kv_tokens_window`` (those a window layer reads, the sum
of min(seen + n, window + n - 1)).  ``ds.serve.readback`` of the same
step (the same ``sid``): ``moe_experts_touched``, the experts that took
at least one row, summed over the expert layers.  Counted: the projections
(q, k, v, the output gate, o), the router, k experts and the shared
expert a token, a dense layer's MLP, the output head, attention.  A
query attends at least one key for every cached token its layer reads,
so attention's operations are counted from the two sums: exact for a
decode token, a lower bound for a prefill chunk.  Not counted: norms,
the per-head QK-norm, sigmoids, softmax, top-k, the routing's sort and
gathers, activations, rotary.
"""

from benchmarks.lib import arith, program_spans, trace

STAGE, READBACK = "ds.serve.stage", "ds.serve.readback"
STEP_KEYS = ("n_tokens", "n_seqs", "kv_tokens_full", "kv_tokens_window")
TOUCHED = "moe_experts_touched"
WINDOW_KIND = "sliding_attention"


def model(config: dict) -> dict:
    return {**config, **config.get("arith", {})}


def _gated(m) -> int:
    return 3 if m.get("gated_mlp") else 2


def attn_params(m) -> int:
    """One layer's attention projections: q, o and the output gate are
    d x H*D each, k and v d x Hkv*D each."""
    d, h, hkv, hd = arith._dims(m)[:4]
    return d * h * hd * (3 if m.get("attn_gate") else 2) + 2 * d * hkv * hd


def expert_params(m) -> int:
    return _gated(m) * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m) -> int:
    return _gated(m) * m["hidden_size"] * m["intermediate_size"]


def layers(m):
    """(dense layers, expert layers, window layers, full layers)."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    dense = m["num_dense_layers"]
    window = sum(k == WINDOW_KIND for k in kinds)
    return dense, len(kinds) - dense, window, len(kinds) - window


def kv_token_bytes(m, kv_bytes: int = 2) -> int:
    """One cached token's keys and values in ONE layer."""
    _, _, hkv, hd = arith._dims(m)[:4]
    return 2 * hkv * hd * kv_bytes


def step_flops(m, n_tokens: int, kv_full: int, kv_window: int,
               logit_rows: int) -> float:
    d, h, _, hd = arith._dims(m)[:4]
    dense, sparse, window, full = layers(m)
    shared = m.get("num_shared_experts", 0)
    per_token = ((dense + sparse) * attn_params(m)
                 + dense * dense_mlp_params(m)
                 + sparse * (d * m["num_experts"]
                             + (m["num_experts_per_tok"] + shared)
                             * expert_params(m)))
    return (2.0 * n_tokens * per_token
            + 4.0 * h * hd * (full * kv_full + window * kv_window)
            + 2.0 * logit_rows * d * m["vocab_size"])


def step_bytes(m, n_tokens: int, kv_full: int, kv_window: int,
               touched: int, weight_bytes: int = 2,
               kv_bytes: int = 2) -> float:
    """Least HBM traffic of one step: every layer's attention weights, a
    dense layer's MLP, an expert layer's router and shared expert, the
    ``touched`` experts (summed over the expert layers), the head, once
    each; the cached keys and values each layer has to read, by its
    kind; the new tokens' keys and values written in every layer; their
    embedding rows read."""
    d = m["hidden_size"]
    dense, sparse, window, full = layers(m)
    shared = m.get("num_shared_experts", 0)
    weights = ((dense + sparse) * attn_params(m)
               + dense * dense_mlp_params(m)
               + sparse * (d * m["num_experts"] + shared * expert_params(m))
               + touched * expert_params(m)
               + d * m["vocab_size"])
    kv = kv_token_bytes(m, kv_bytes)
    return (weights * weight_bytes
            + (full * kv_full + window * kv_window) * kv
            + (dense + sparse) * n_tokens * kv
            + n_tokens * d * weight_bytes)


def expert_gemm_flops(m, n_tokens: int) -> float:
    """The routed experts' projections alone, the expert layers of one
    step (the grouped kernel; the shared expert is a plain product)."""
    return 2.0 * n_tokens * layers(m)[1] * m["num_experts_per_tok"] \
        * expert_params(m)


def expert_gemm_bytes(m, n_tokens: int, touched: int,
                      weight_bytes: int = 2) -> float:
    """``touched`` experts' weights (summed over the expert layers), and
    every assignment's row in and out of each projection."""
    rows = layers(m)[1] * n_tokens * m["num_experts_per_tok"]
    per_row = _gated(m) * (m["hidden_size"] + m["moe_intermediate_size"])
    return weight_bytes * (touched * expert_params(m) + rows * per_row)


def window_attn_flops(m, kv_window: int) -> float:
    _, h, _, hd = arith._dims(m)[:4]
    return 4.0 * h * hd * layers(m)[2] * kv_window


def window_attn_bytes(m, n_tokens: int, kv_window: int,
                      kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """The window layers' kernel alone: the keys and values inside the
    windows, the queries in and the outputs out."""
    _, h, _, hd = arith._dims(m)[:4]
    return layers(m)[2] * (kv_window * kv_token_bytes(m, kv_bytes)
                           + 2 * n_tokens * h * hd * act_bytes)


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last (whose device work may fall behind the window's end), as the
    program's own spans tell them: ``[{n_tokens, n_seqs, kv_tokens_full,
    kv_tokens_window, moe_experts_touched}]``, the last from the step's
    ``ds.serve.readback`` span, wherever in the file it lies.  Empty
    where the trace has no such spans or they lack these counts (a
    program from before they were written)."""
    if "_hybrid_steps" not in rec:
        steps = []
        path = trace.find_xplane(rec["trace_dir"]) \
            if rec.get("kind") == "serve" and rec.get("trace_dir") else None
        window = (rec.get("trace") or {}).get("window")
        if path and window:
            lo, hi = window
            threads, _, _ = program_spans.read(path)
            evs = [ev for line in threads.values() for ev in line]
            touched = {st["sid"]: st[TOUCHED] for _, _, nm, st in evs
                       if nm == READBACK and TOUCHED in st}
            spans = sorted((s, st) for s, e, nm, st in evs
                           if nm == STAGE and lo <= s and e <= hi)
            steps = [{**{k: float(st[k]) for k in STEP_KEYS},
                      TOUCHED: float(touched[st["sid"]])}
                     for _, st in spans[:-1]
                     if all(k in st for k in STEP_KEYS)
                     and st.get("sid") in touched]
        rec["_hybrid_steps"] = steps
    return rec["_hybrid_steps"]


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of the roofline time of
    ``per_step(m, step) -> (flops, bytes)`` → ``(steps, least seconds,
    how many steps each bound decides)``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec or "layer_types" not in rec["config"]:
        return None
    m = model(rec["config"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        sec, which = arith.roofline_seconds(*per_step(m, s), rec["peaks"])
        least += sec
        bounds[which] += 1
    return len(steps), least, bounds
