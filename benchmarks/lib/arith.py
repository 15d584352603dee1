"""Operations and bytes a model's step REQUIRES, from its shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  ``m`` is a configuration file's ``model`` block (the
published ``config.json`` keys).  Counted: matrix multiplications and
attention.  Not counted: norms, activations, rotary, softmax, the
embedding gather, recomputation.  (Copied in spirit from bench.py's
``6 * n_params + 12 * L * d * seq``; that form counts the masked half
of causal attention and the embedding table, this one does not.)
"""


def _dims(m):
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    hkv = m.get("num_key_value_heads") or h
    hd = m.get("head_dim") or d // h
    return d, h, hkv, hd, m["intermediate_size"], m["num_hidden_layers"], \
        m["vocab_size"]


def layer_matmul_params(m) -> int:
    """Weights of one layer that a token is multiplied with."""
    d, h, hkv, hd, ff, _, _ = _dims(m)
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = (3 if m.get("gated_mlp") else 2) * d * ff
    return attn + mlp


def matmul_params(m) -> int:
    """All weights a token is multiplied with: the layers and the output
    head.  The embedding table is a gather, not a multiplication."""
    d, _, _, _, _, layers, vocab = _dims(m)
    return layers * layer_matmul_params(m) + d * vocab


def param_count(m) -> int:
    """Every parameter, for memory: matmul weights, embedding, biases
    and norms."""
    d, h, hkv, hd, ff, layers, vocab = _dims(m)
    n = matmul_params(m) + vocab * d
    norm = d * (2 if m.get("norm") == "layernorm" else 1)
    per_layer = norm * (1 if m.get("single_norm") else 2)
    if m.get("attn_bias"):
        per_layer += h * hd + 2 * hkv * hd + d
    if m.get("mlp_bias"):
        per_layer += ff + d
    return n + layers * per_layer + norm


def train_flops_per_token(m, seq: int) -> float:
    """Forward plus backward, no recomputation: 6 per weight, and causal
    attention's two products (QK^T, PV) over the (seq+1)/2 keys a query
    sees on average: 2*2*H*D*(seq+1)/2 forward, three times that with
    the backward pass."""
    _, h, _, hd, _, layers, _ = _dims(m)
    return 6.0 * matmul_params(m) + 6.0 * layers * h * hd * (seq + 1)


def kv_bytes_per_token(m, kv_bytes: int = 2) -> int:
    _, _, hkv, hd, _, layers, _ = _dims(m)
    return 2 * hkv * hd * kv_bytes * layers


def serve_step_flops(m, n_tokens: int, qk_pairs: int, logit_rows: int) -> float:
    """One serving step: ``n_tokens`` through every layer's weights,
    ``qk_pairs`` (query, key) pairs of attention (for a chunk of n tokens
    after s seen: n*s + n(n+1)/2), ``logit_rows`` rows through the head."""
    d, h, _, hd, _, layers, vocab = _dims(m)
    return (2.0 * n_tokens * layers * layer_matmul_params(m)
            + 4.0 * layers * h * hd * qk_pairs
            + 2.0 * logit_rows * d * vocab)


def serve_step_bytes(m, n_tokens: int, ctx_tokens: int,
                     weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least HBM traffic of one serving step: every matmul weight once,
    the cached keys and values of each scheduled sequence once
    (``ctx_tokens`` = sum of their context lengths after the step), the
    new tokens' keys and values written, their embedding rows read."""
    d = m["hidden_size"]
    return (matmul_params(m) * weight_bytes
            + (ctx_tokens + n_tokens) * kv_bytes_per_token(m, kv_bytes)
            + n_tokens * d * weight_bytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound)."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
