"""Programs compiled inside the window: ``training_compiles_total`` after
minus before, plus XLA compiles of any kind seen by jax.monitoring.
Must read 0."""


def read(rec):
    return rec["window_compiles"] if rec["kind"] == "train" else None
