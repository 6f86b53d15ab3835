"""Median device time of one run of the program that held the device longest in the traced window (the train step), from the trace's XLA Modules line."""


def read(record: dict):
    programs = record.get("trace", {}).get("programs")
    if not programs:
        return None
    return programs[0][3] * 1e3
