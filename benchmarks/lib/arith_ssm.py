"""Operations and bytes that a serving step of a model with a Mamba-2
mixer beside its attention in every block requires, from its shapes and
from what the program's spans say of the step.  Beside ``arith.py``,
which knows no mixer: it would leave out a third of a decode step's
bytes (the recurrent states) and read the step over its true share.

A lower bound on what ANY implementation must do, whatever implements
it.  ``m`` is a configuration file's published keys with its ``arith``
block laid over them: ``hidden_size`` (d), ``num_attention_heads`` x
``head_dim`` (H * D, which need not be d), ``num_key_value_heads``,
``intermediate_size``, ``mamba_d_ssm``, ``mamba_n_heads`` x
``mamba_d_head``, ``mamba_n_groups``, ``mamba_d_state``,
``mamba_d_conv``, ``mamba_chunk_size``, ``state_bytes`` (the stored
type of a state).

A step is its ``ds.serve.stage`` span (``telemetry/tracer.py``):
``n_tokens``, ``n_seqs``, ``kv_tokens_full`` (the cached tokens an
attention layer reads), ``state_rows`` (sequences whose state the step
advances by one token), ``scan_tokens`` (the tokens of its longer runs),
``state_starts`` (runs that begin at position 0), ``state_replays``
(one-token rows fed again).

Counted, a layer: a one-token row reads and writes its state in the
stored type, the convolution's tail (width - 1 inputs), and x, B, C, dt,
z in and y out; a scanned token costs the chunked form's least products
(inside a chunk the causal half of C B^T and of its product with x; B^T
x into the chunk's state; C times the state coming in) and x, B, C, dt,
z in and y out, a longer run writes its last state and, where it does
not start at position 0, reads its first; the mixer's two projections,
attention's four (with H * D), the gated MLP, attention over the cached
tokens; once a step the head over the rows that sample.  Not counted:
norms, activations, rotary, softmax, the convolution's products, the
decay's exponentials.
"""

from benchmarks.lib import arith, program_spans, trace

STAGE = "ds.serve.stage"
STEP_KEYS = ("n_tokens", "n_seqs", "kv_tokens_full", "state_rows",
             "scan_tokens", "state_starts", "state_replays")
SCOPES = ("ssm_update", "ssm_scan", "ssm_conv", "ssm_in", "ssm_out")


def model(config: dict) -> dict:
    return {**config, **config.get("arith", {})}


def conv_channels(m) -> int:
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def in_proj(m) -> int:
    """Columns of the mixer's input projection: z, xBC, dt."""
    return m["mamba_d_ssm"] + conv_channels(m) + m["mamba_n_heads"]


def mixer_params(m) -> int:
    """The mixer's two projections, one layer."""
    d = m["hidden_size"]
    return d * in_proj(m) + m["mamba_d_ssm"] * d


def state_elements(m) -> int:
    """One sequence's state in one layer."""
    return m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]


def tail_elements(m) -> int:
    """The raw inputs the convolution has to carry: width - 1."""
    return (m["mamba_d_conv"] - 1) * conv_channels(m)


def row_io_elements(m) -> int:
    """x, B, C, dt, z in and y out of the recurrence, one token."""
    return in_proj(m) + m["mamba_d_ssm"]


def state_bytes_per_seq(m) -> int:
    """A sequence's state and tail over all layers, as stored."""
    return m["num_hidden_layers"] * m["state_bytes"] \
        * (state_elements(m) + tail_elements(m))


def update_bytes(m, rows: float, act_bytes: int = 2) -> float:
    """The one-token update of ``rows`` sequences, ALL layers: the state
    and the tail read and written, the row's inputs and output."""
    per_row = 2 * m["state_bytes"] * (state_elements(m) + tail_elements(m)) \
        + act_bytes * row_io_elements(m)
    return m["num_hidden_layers"] * rows * per_row


def update_flops(m, rows: float) -> float:
    """decay * S + (dt x) (outer) B, then S C: five a state element."""
    return 5.0 * m["num_hidden_layers"] * rows * state_elements(m)


def scan_flops(m, tokens: float) -> float:
    """The chunked form's least products for ``tokens``, ALL layers."""
    q = (m["mamba_chunk_size"] + 1) / 2.0
    hp = m["mamba_n_heads"] * m["mamba_d_head"]
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    per_token = 2.0 * q * (gn + hp) + 4.0 * state_elements(m)
    return m["num_hidden_layers"] * tokens * per_token


def scan_bytes(m, tokens: float, runs: float, starts: float,
               act_bytes: int = 2) -> float:
    """The longer runs' least traffic, ALL layers: every token's inputs
    and output, a run's last state written, its first read unless it
    starts at position 0."""
    continuing = max(0.0, runs - starts)
    return m["num_hidden_layers"] * (
        tokens * act_bytes * row_io_elements(m)
        + (runs + continuing) * m["state_bytes"]
        * (state_elements(m) + tail_elements(m)))


def scan_runs(s) -> float:
    """The runs of several tokens a step holds."""
    return max(0.0, s["n_seqs"] - s["state_rows"] - s["state_replays"])


def layer_params(m) -> int:
    """Every matmul weight of one layer: attention (H * D), the gated
    MLP, the mixer's projections."""
    return arith.layer_matmul_params(m) + mixer_params(m)


def step_flops(m, s) -> float:
    d, h, _, hd = arith._dims(m)[:4]
    layers = m["num_hidden_layers"]
    return (2.0 * s["n_tokens"] * layers * layer_params(m)
            + 4.0 * layers * h * hd * s["kv_tokens_full"]
            + update_flops(m, s["state_rows"])
            + scan_flops(m, s["scan_tokens"])
            + 2.0 * s["n_seqs"] * d * m["vocab_size"])


def step_bytes(m, s, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least HBM traffic of one step: every layer's weights and the head
    once; the cached keys and values an attention layer reads, and the
    new tokens' written; the states (``update_bytes``, ``scan_bytes``);
    the new tokens' embedding rows."""
    d = m["hidden_size"]
    layers = m["num_hidden_layers"]
    return ((layers * layer_params(m) + d * m["vocab_size"]) * weight_bytes
            + (s["kv_tokens_full"] + s["n_tokens"])
            * arith.kv_bytes_per_token(m, kv_bytes)
            + update_bytes(m, s["state_rows"])
            + scan_bytes(m, s["scan_tokens"], scan_runs(s),
                         min(s["state_starts"], scan_runs(s)))
            + s["n_tokens"] * d * weight_bytes)


def _xplane(rec):
    return trace.find_xplane(rec["trace_dir"]) \
        if rec.get("kind") == "serve" and rec.get("trace_dir") else None


def traced_steps(rec) -> list:
    """The steps staged wholly inside the traced window, all but the
    last (whose device work may fall behind the window's end), as the
    program's own ``ds.serve.stage`` spans tell them.  Empty where the
    trace has no such spans or they lack these counts (a program without
    recurrent layers, or from before they were written)."""
    if "_ssm_steps" not in rec:
        steps = []
        path = _xplane(rec)
        window = (rec.get("trace") or {}).get("window")
        if path and window:
            lo, hi = window
            threads, _, _ = program_spans.read(path)
            spans = sorted((s, st) for line in threads.values()
                           for s, e, nm, st in line
                           if nm == STAGE and lo <= s and e <= hi)
            steps = [{k: float(st[k]) for k in STEP_KEYS}
                     for _, st in spans[:-1]
                     if all(k in st for k in STEP_KEYS)]
        rec["_ssm_steps"] = steps
    return rec["_ssm_steps"]


def least_seconds(rec, per_step):
    """Sum over ``traced_steps`` of the roofline time of
    ``per_step(m, step) -> (flops, bytes)`` → ``(steps, least seconds,
    how many steps each bound decides)``; None without such a step."""
    steps = traced_steps(rec)
    if not steps or "peaks" not in rec \
            or "mamba_d_ssm" not in rec["config"]:
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
    mixer's scopes (an operation counts where one of its JAX paths holds
    the scope as a whole component), and ``busy_s``, the union of all
    its operations there.  {} where the trace holds none of them."""
    if "_ssm_scopes" not in rec:
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
        rec["_ssm_scopes"] = out
    return rec["_ssm_scopes"]
