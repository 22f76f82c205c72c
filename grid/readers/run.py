"""Metrics of the run as a whole."""


def setup_s(record, trace=None) -> float:
    return record["setup_s"]
