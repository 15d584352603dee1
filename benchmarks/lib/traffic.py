"""The one general traffic generator.  No JAX here: the load generator's
child process imports this.

**Every seed does the same work.**  A traffic file gives distributions;
each is turned into a fixed grid of quantiles (``n`` midpoints
``(i + 0.5) / n``), so the multiset of prompt lengths, answer lengths,
inter-arrival times and start-up phases is the same for every seed.
``--seed`` only permutes each grid's order (which pairs them up) and
draws the token ids.  Lists are cycled, so a faster program is simply
further down the list.

A mix may go further and fix the order too (``order_seed`` in its file):
then every seed replays the same lengths and arrivals in the same order,
and the seed draws only the token ids (and the weights).  That is for
mixes whose metric is a tail of a queue: the tail follows where the long
requests cluster, which a 45 s window samples only a few times, so two
orders differ by far more than two runs of one order.
"""

import math

import numpy as np


def grid(dist: dict, n: int) -> np.ndarray:
    """``n`` quantile midpoints of ``dist`` in ascending order."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "const":
        return np.full(n, float(dist["value"]))
    if kind == "uniform":
        return dist["lo"] + (dist["hi"] - dist["lo"]) * q
    if kind == "loguniform":
        return dist["lo"] * (dist["hi"] / dist["lo"]) ** q
    if kind == "exponential":
        return -dist["mean"] * np.log1p(-q)
    raise ValueError(f"unknown distribution {kind!r}")


def int_grid(dist: dict, n: int) -> np.ndarray:
    return np.maximum(1, np.rint(grid(dist, n))).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    # seeds go a little past 2**31; SeedSequence takes any non-negative int
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


class Requests:
    """The request list of a serving mix: entry ``k`` (cycled) gives
    prompt length, answer length, the gap before the next arrival and,
    for the closed loop's first round, the phase in (0, 1]."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        n = int(traffic["grid"])
        self.n = n
        self.vocab = vocab
        self._seed = seed                        # token ids: always the run's
        seed = traffic.get("order_seed", seed)   # orders: the mix's, if fixed
        self.prompt_len = rng_for(seed, 1).permutation(
            int_grid(traffic["prompt_tokens"], n))
        self.answer_len = rng_for(seed, 2).permutation(
            int_grid(traffic["answer_tokens"], n))
        rate = traffic.get("rate_per_s")
        burst = int(traffic.get("burst", 1))
        if rate:
            # bursts of b: b arrivals together, gaps b times as long
            gaps = grid({"dist": "exponential", "mean": burst / rate},
                        -(-n // burst))
            gaps = rng_for(seed, 3).permutation(gaps)
            self.gap_s = np.repeat(gaps, burst)[:n]
            self.gap_s[np.arange(n) % burst != burst - 1] = 0.0
        else:
            self.gap_s = np.zeros(n)
        self.phase = rng_for(seed, 4).permutation((np.arange(n) + 1.0) / n)
        shared = traffic.get("shared_prefix") or {}
        self.shared_share = float(shared.get("share", 0.0))
        k = int(shared.get("prefixes", 1))
        plen = int(shared.get("tokens", 0))
        self.prefixes = [rng_for(seed, 100 + i).integers(
            0, vocab, plen).tolist() for i in range(k)] if plen else []
        self._shared_pick = rng_for(seed, 5).permutation(
            (np.arange(n) + 0.5) / n)

    def entry(self, k: int) -> dict:
        i = k % self.n
        return {"k": k, "prompt_len": int(self.prompt_len[i]),
                "answer_len": int(self.answer_len[i]),
                "gap_s": float(self.gap_s[i]),
                "phase": float(self.phase[i])}

    def tokens(self, k: int, length: int) -> list:
        """Token ids of request ``k``: fresh for every k (a cycled list
        repeats lengths, never content, so the prefix cache sees no hit
        the mix did not ask for).  With ``shared_prefix``, that share of
        requests starts with one of the fixed prefixes."""
        toks = rng_for(self._seed, 1000 + k).integers(
            0, self.vocab, length).tolist()
        if self.prefixes and self._shared_pick[k % self.n] < self.shared_share:
            p = self.prefixes[k % len(self.prefixes)]
            toks = (p + toks)[:max(length, 1)]
        return toks

    def multisets(self) -> dict:
        return {"prompt_len": sorted(self.prompt_len.tolist()),
                "answer_len": sorted(self.answer_len.tolist()),
                "gap_s": sorted(np.round(self.gap_s, 9).tolist()),
                "phase": sorted(self.phase.tolist())}


def closed_first_round(reqs: Requests, clients: int):
    """The closed loop starts in steady state: client ``c``'s first
    request is entry ``c`` caught part-way through its answer.  With
    ``done = floor((1 - phase) * answer_len)`` tokens already behind it,
    its prompt is that much longer and ``max_tokens`` that much shorter,
    so contexts and remaining lengths are spread as they are mid-run.
    The phases are a fixed grid, permuted by the seed."""
    out = []
    for c in range(clients):
        e = reqs.entry(c)
        remaining = max(1, math.ceil(e["phase"] * e["answer_len"]))
        done = e["answer_len"] - remaining
        out.append({**e, "prompt_len": e["prompt_len"] + done,
                    "answer_len": remaining})
    return out


def token_stream(seed: int, vocab: int, n_tokens: int) -> np.ndarray:
    """The training job's packed token stream."""
    return rng_for(seed, 7).integers(0, vocab, n_tokens, dtype=np.int32)
