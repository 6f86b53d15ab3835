"""Score entries the sliding-window layers' kernels visit (the block pairs their tables walk, a head and sequence) over the entries the mask keeps: the program's static counter ``attn_pairs`` for the kind ``sliding_attention``, handed over by the driver. 1 is no waste; the whole triangle at 8192 tokens and a 512-token window reads 8.3. None where the record states none."""


def read(record: dict):
    pairs = record.get("attn_pairs", {}).get("sliding_attention")
    return pairs["visited"] / pairs["needed"] if pairs else None
