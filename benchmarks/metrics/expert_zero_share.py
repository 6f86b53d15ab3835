"""Share of the entries of the held rows' gated hidden activations, relu(W_g u) * W_u u, that are exactly zero in the routed experts: the program's counter ``moe_act_zero_share``, the mean over the expert layers and the window's steps, in percent (a round's padding rows are not counted). About 50 at seeded weights: a ReLU gate closes on a negative pre-activation. None where the record states none (a program whose experts' gate is not a ReLU, or that lacks the counter)."""


def read(record: dict):
    share = record.get("moe", {}).get("act_zero_share")
    return None if share is None else 100.0 * share
