"""Model step: the share of the blocks at or before a query that the sparse
layers read, over the window, from the program's own counters: 100 x
``sparse_blocks_read`` / ``sparse_blocks_visible`` (both summed over real
queries, key heads and sparse layers, prefills and steps alike). About a
sixth where every prompt is three times ``dense_len``; 100 if a path ever
attends densely. None where the program counts no such blocks."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    read_, visible = b.get("sparse_blocks_read", 0), b.get("sparse_blocks_visible", 0)
    if not read_ or not visible:
        return None
    return 100.0 * read_ / visible
