"""Seconds from process start to the start of the measured window."""


def read(record):
    return record["setup_s"]
