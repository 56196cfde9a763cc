"""XLA programs the set-up built, compiled or loaded from the
persistent compilation cache."""


def read(record):
    return record["setup_programs"]
