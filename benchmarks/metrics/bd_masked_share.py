"""Share of the noisy copy's positions that are masked and so carry loss: the program's counter ``bd_masked_share``, the mean over the window's steps, in percent. About 50 under t ~ U[eps, 1]. None where the record states none (a program without the counter)."""


def read(record: dict):
    share = record.get("bd", {}).get("masked_share")
    return None if share is None else 100.0 * share
