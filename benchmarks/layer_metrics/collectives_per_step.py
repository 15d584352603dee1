"""Collective operations in the compiled train step's HLO text."""


def read(rec):
    c = rec.get("hlo_collectives")
    return None if not c else c["total"]
