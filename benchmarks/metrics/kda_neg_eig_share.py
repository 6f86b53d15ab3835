"""Share of (token, head) pairs in the linear-attention layers whose delta-rule step has beta > 1, so that the transition I - beta k k^T has a negative eigenvalue along k: the program's counter ``kda_neg_eig_share``, the mean over the layers and the window's steps, in percent. 0 where beta = sigmoid (no step can pass 1); about 50 at random weights where beta = 2 sigmoid. None where the record states none (a program without the counter)."""


def read(record: dict):
    share = record.get("kda", {}).get("neg_eig_share")
    return None if share is None else 100.0 * share
