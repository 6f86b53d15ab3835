"""Share of the window the loop spent in next(iterator), host clock."""


def read(record: dict):
    if "data_wait_s" not in record:
        return None
    return 100.0 * record["data_wait_s"] / record["window_s"]
