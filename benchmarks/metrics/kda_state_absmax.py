"""Largest magnitude in any linear-attention layer's recurrent state at the end of the sequences, the largest over the window's steps: the program's counter ``kda_state_absmax``, fetched after the window. A state that grows without bound says the decay or the delta rule's correction is lost."""


def read(record: dict):
    return record.get("kda", {}).get("state_absmax")
