"""Operations and bytes a sparse-expert model's serving step REQUIRES,
from its shapes.  Beside ``arith.py``, whose ``3 * d * intermediate_size``
a layer is ONE expert of such a model: it would count an eighth of the
operations (8 experts a token) and a sixty-fourth of the weights (all 64
experts are read in a step).

``m`` is a configuration file's published keys with its ``arith`` block
laid over them: ``num_experts`` (E), ``num_experts_per_tok`` (k),
``intermediate_size`` (w, one expert's width), gated.  Counted: matrix
multiplications (attention's projections, the router, k experts a
token), attention, the output head.  Not counted: norms (QK-norm too),
softmax, top-k, the sort and gathers of the routing, activations,
rotary.
"""

from benchmarks.lib import arith


def _projections(m) -> int:
    return 3 if m.get("gated_mlp") else 2


def expert_params(m) -> int:
    """One expert: its two or three projections."""
    return _projections(m) * m["hidden_size"] * m["intermediate_size"]


def layer_attn_params(m) -> int:
    """``arith.layer_matmul_params`` counts attention and one expert."""
    return arith.layer_matmul_params(m) - expert_params(m)


def layer_params(m) -> int:
    """Every matmul weight of one layer: attention, router, all experts."""
    return layer_attn_params(m) + m["hidden_size"] * m["num_experts"] \
        + m["num_experts"] * expert_params(m)


def experts_touched(m, n_tokens: int) -> int:
    """Experts whose weights a step of ``n_tokens`` has to read: every
    one, once a step holds as many assignments as there are experts (the
    chance that one of 64 gets none of 900 is 5e-5)."""
    return min(m["num_experts"], n_tokens * m["num_experts_per_tok"])


def moe_step_flops(m, n_tokens: int, qk_pairs: int, logit_rows: int) -> float:
    """One serving step: ``n_tokens`` through every layer's attention
    projections, router and k experts; ``qk_pairs`` (query, key) pairs of
    attention; ``logit_rows`` rows through the head."""
    d, h, _, hd, _, layers, vocab = arith._dims(m)
    per_token = layer_attn_params(m) + d * m["num_experts"] \
        + m["num_experts_per_tok"] * expert_params(m)
    return (2.0 * n_tokens * layers * per_token
            + 4.0 * layers * h * hd * qk_pairs
            + 2.0 * logit_rows * d * vocab)


def moe_step_bytes(m, n_tokens: int, ctx_tokens: int,
                   weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least HBM traffic of one serving step: attention's weights, the
    router and every expert touched once a layer, the head once, the
    cached keys and values of each scheduled sequence once, the new
    tokens' keys and values written, their embedding rows read."""
    d, _, _, _, _, layers, vocab = arith._dims(m)
    per_layer = layer_attn_params(m) + d * m["num_experts"] \
        + experts_touched(m, n_tokens) * expert_params(m)
    return ((layers * per_layer + d * vocab) * weight_bytes
            + (ctx_tokens + n_tokens) * arith.kv_bytes_per_token(m, kv_bytes)
            + n_tokens * d * weight_bytes)


def expert_gemm_flops(m, n_tokens: int) -> float:
    """The expert projections alone, all layers of one step."""
    return 2.0 * n_tokens * m["num_hidden_layers"] \
        * m["num_experts_per_tok"] * expert_params(m)


def expert_gemm_bytes(m, n_tokens: int, weight_bytes: int = 2) -> float:
    """The expert projections alone: every touched expert's weights once
    a layer, and each projection's rows in and out (k rows a token)."""
    rows = n_tokens * m["num_experts_per_tok"]
    per_row = _projections(m) * (m["hidden_size"] + m["intermediate_size"])
    return m["num_hidden_layers"] * weight_bytes * (
        experts_touched(m, n_tokens) * expert_params(m) + rows * per_row)


def traced_least_seconds(rec, per_step):
    """The roofline readers' shared sum: over the steps that lie wholly
    inside the traced window, ``per_step(m, step) -> (flops, bytes)`` →
    ``(steps, least seconds, how many steps each bound decides)``; None
    where the record holds no sparse-expert model or no such step."""
    m = {**rec["config"], **rec["config"].get("arith", {})}
    t0, t1 = rec["trace_window"]
    steps = [s for s in rec["steps"] if t0 <= s["t0"] and s["t1"] <= t1]
    if "num_experts" not in m or not steps:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        sec, which = arith.roofline_seconds(*per_step(m, s), rec["peaks"])
        least += sec
        bounds[which] += 1
    return len(steps), least, bounds
