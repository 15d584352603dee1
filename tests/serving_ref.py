"""The strict reference the serving tests compare ``generate()`` and the
step that runs ahead with: a caller that puts concrete tokens and reads
every step back in the call that launched it.  A step that holds a
caller-fed row is launched with nothing in flight (``step()``,
``reason="caller_fed"``), so this is the strict path INSIDE the served
loop: same compiled step, no launch ahead, nothing configures it."""


def strict_generate(eng, prompts, sampling, rng=None, after_step=None):
    """What ``eng.generate(prompts, sampling, rng)`` returns, by ``put``
    of each sampled token and one strict step a round.  Reads the
    engine's per-step token LISTS (``_step``; ``step()`` is their last
    elements), so a speculative engine's accepted windows arrive whole.
    ``after_step(n)`` runs after the n-th round (a test's preemption,
    say)."""
    done = {uid: [] for uid in prompts}
    active = {uid for uid, p in prompts.items() if eng.put(uid, list(p))}
    n = 0
    while active:
        outs = eng._step(rng, sampling)
        active -= eng._drain_reaped()
        for uid, toks in outs.items():
            if uid not in active:
                continue
            row = done[uid]
            for tok in toks:
                row.append(tok)
                if tok == sampling.stop_token \
                        or len(row) >= sampling.max_new_tokens:
                    active.discard(uid)
                    eng.flush(uid)
                    break
            else:
                eng.put(uid, [toks[-1]])
        n += 1
        if after_step is not None:
            after_step(n)
        assert n < 5000, "strict_generate() did not terminate"
    return done
