"""Serving dispatches launched in the window per solve emitted in it."""


def read(record):
    if not record["dispatches"] or not record["solves_in_window"]:
        return None
    return len(record["dispatches"]) / record["solves_in_window"]
