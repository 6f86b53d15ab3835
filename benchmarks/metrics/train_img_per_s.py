"""Images of the steps completed in the window, per second and chip; the window ends on block_until_ready."""


def read(record: dict):
    if "step_s" not in record:
        return None
    return record["images"] / record["window_s"] / record["chips"]
