"""Output tokens RECEIVED BY CLIENTS inside the window, by each token's
arrival time on the wire, over the window.  Not tokens of completed
requests.  Client's clock (the load generator's process)."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    w = rec["window"]
    n = sum(1 for r in rec["requests"] for t in r["token_t"]
            if w["t_open"] <= t < w["t_close"])
    return n / w["seconds"]
