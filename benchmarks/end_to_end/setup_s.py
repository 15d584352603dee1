"""Process start to window open: imports, weights, compile or cache load,
engine, warm-up, the reference comparison.  Host clock."""


def read(rec):
    return rec["setup_s"]
