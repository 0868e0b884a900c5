"""Compile layer: the first call of the cell's program in this process --
trace, lowering and the compile or the cache load."""


def read(m):
    return m["spans"].get("first_call_s")
