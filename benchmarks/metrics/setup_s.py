"""Process start to the start of the measured window, host clock."""


def read(record: dict):
    return record.get("setup_s")
