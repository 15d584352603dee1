"""Training speed of the whole job (all chips together): tokens of the
steps that completed in the window over the time from the first such
step's start to the last one's end.  Host clock; each end is a
``block_until_ready``."""


def read(rec):
    if rec["kind"] != "train" or not rec["steps_done"]:
        return None
    return len(rec["steps_done"]) * rec["tokens_per_step"] / rec["span_s"]
