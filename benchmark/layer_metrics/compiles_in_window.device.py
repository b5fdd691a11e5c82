"""Programs JAX built (compiled or fetched) between the first and the
last timed round. Must read 0: a run that compiles in its window has
timed the compiler."""


def read(record):
    return record.get("compiles_in_window")
