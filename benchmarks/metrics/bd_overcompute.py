"""Score entries the block-diffusion core's kernels visit (the block pairs their tables walk; of a pair a mask cuts, the sub-tiles that hold a visible entry; a head and sequence) over the entries the pattern shows: the program's static counter ``attn_pairs`` for the kind ``block_diffusion``, handed over by the driver. 1 is no waste; every visited pair computed whole at 8192 tokens in blocks of 4 would read 1.25. None where the record states none."""


def read(record: dict):
    pairs = record.get("attn_pairs", {}).get("block_diffusion")
    return pairs["visited"] / pairs["needed"] if pairs else None
