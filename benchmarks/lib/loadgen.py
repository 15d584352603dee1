"""The serving cells' load generator: a child process that never imports
JAX.  One thread, asyncio, one HTTP/1.1 connection per request to
``gateway.Gateway`` on loopback, SSE streaming.  (The wire handling is
copied from tools/loadgen.py ``http_completion``, which times TTFT from
the moment of sending over a real socket; that one is synchronous, one
thread per request inside the server's own process.)

Protocol with the parent: arguments on the command line; on stdout one
JSON line per event (``open``, ``close``, ``done``); per-request records
to ``--out`` as JSON lines.  Clocks are ``time.monotonic()``, which on
Linux is one clock for every process of the machine.
"""

import argparse
import asyncio
import json
import sys
import time

if __package__ in (None, ""):
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
from benchmarks.lib import traffic as T  # noqa: E402

UID_BASE = 1 << 20      # request uids count up from here


def say(**kw):
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


async def one_request(host, port, uid, prompt, max_tokens, due, rec):
    """POST /v1/completions, stream, stamp every token's arrival."""
    body = json.dumps({"uid": uid, "prompt": prompt,
                       "max_tokens": max_tokens, "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode("ascii")
    rec.update(uid=uid, due=due, n_prompt=len(prompt),
               max_tokens=max_tokens, code=None, token_t=[], finish=None,
               error=None, cut=False)
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        rec["sent"] = time.monotonic()
        writer.write(head + body)
        await writer.drain()
        status = await reader.readline()
        rec["code"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec["code"] != 200:
            return rec
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                break
            choice = json.loads(data)["choices"][0]
            if choice["token"] is not None:
                rec["token_t"].append(time.monotonic())
            if choice["finish_reason"] is not None:
                rec["finish"] = choice["finish_reason"]
    except asyncio.CancelledError:
        rec["cut"] = True        # the run ended with this one in flight
        raise
    except (OSError, ValueError, IndexError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
    return rec


async def run(args):
    traffic = json.load(open(args.traffic))
    reqs = T.Requests(traffic, args.seed, args.vocab)
    records, tasks = [], set()
    uid = [UID_BASE]
    t0 = time.monotonic() + 0.05
    t_open = t0 + args.warmup
    t_close = t_open + args.seconds
    sessions = traffic.get("sessions")

    def launch(entry, due, prompt=None):
        rec = {"k": entry["k"]}
        records.append(rec)
        uid[0] += 1
        prompt = prompt or reqs.tokens(entry["k"], entry["prompt_len"])
        return one_request(args.host, args.port, uid[0], prompt,
                           entry["answer_len"], due, rec)

    async def marks():
        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        say(event="open", t=time.monotonic(), due=t_open)
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        say(event="close", t=time.monotonic(), due=t_close)

    marker = asyncio.ensure_future(marks())
    if traffic["kind"] == "closed":
        clients = int(traffic["clients"])
        nxt = [clients]

        async def client(first):
            entry, history = first, None
            while time.monotonic() < t_close:
                rec = await launch(entry, time.monotonic(), history)
                if rec["code"] != 200 or rec["error"]:
                    await asyncio.sleep(0.05)
                k, nxt[0] = nxt[0], nxt[0] + 1
                entry = reqs.entry(k)
                history = None
                if sessions and k % int(sessions["turns"]):
                    # a further turn: the whole conversation so far, then
                    # this turn's new text.  Token values of the answer
                    # do not matter to the work, only their number.
                    prev = reqs.tokens(rec["k"], rec["n_prompt"])
                    history = prev + reqs.tokens(
                        10 ** 6 + k, len(rec["token_t"]) + entry["prompt_len"])
                    history = history[:int(sessions["max_context"])]

        tasks = {asyncio.ensure_future(client(e))
                 for e in T.closed_first_round(reqs, clients)}
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
    else:                                                  # open loop
        due, k = t0, 0
        while due < t_close:
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            e = reqs.entry(k)
            tasks.add(asyncio.ensure_future(launch(e, due)))
            due += e["gap_s"]
            k += 1
        # requests due inside the window are owed their first token and
        # their whole answer: wait for them, up to the drain limit
        if tasks:
            await asyncio.wait(tasks, timeout=args.drain)
    await marker
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    with open(args.out, "w") as f:
        for rec in records:
            if "sent" in rec:
                f.write(json.dumps(rec) + "\n")
    late = [r["sent"] - r["due"] for r in records if "sent" in r]
    say(event="done", t_open=t_open, t_close=t_close, requests=len(late),
        late_ms_max=1e3 * max(late, default=0.0),
        late_ms_mean=1e3 * sum(late) / max(1, len(late)),
        multisets={k: [len(v), float(sum(v))]
                   for k, v in reqs.multisets().items()})


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--host", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--vocab", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--warmup", type=float, required=True)
    p.add_argument("--drain", type=float, default=30.0)
    p.add_argument("--out", required=True)
    asyncio.run(run(p.parse_args()))


if __name__ == "__main__":
    main()
