"""Work of the embed_join join, counted from the job's shapes.

One join level takes ``rows`` partial embeddings of ``width`` matched
vertices and ``cands`` candidates of the next query vertex, which has
``tests`` already-matched neighbors.  The job is, per (row, candidate)
pair, one edge-label test per matched neighbor and one injectivity compare
per matched vertex.  It reads the table slice, the candidate ids and the
edge labels the tests need (each (data vertex, candidate) label at most
once: at most ``min(rows * tests, n)`` data vertices, ``n`` being the
filtered graph's vertex count), writes one count per row, and writes the
next table (``survivors`` rows of ``width + 1`` ids).  How the kernel
evaluates this (a one-hot contraction, two passes over the grid) does not
enter the count.  All values are 4-byte ids or labels.
"""

# programs whose device time is the join's: count pass, emit pass and the
# gather that writes the next table, and the validity grid of the path
# without the count kernel
PROGRAMS = ("_device_join_count", "_device_join_emit", "_device_join_emit_gather",
            "_device_join_valid")


def work(call: dict) -> tuple[int, int]:
    """(operations, bytes) of one recorded call."""
    if call["kind"] == "gather":
        return 0, 4 * call["survivors"] * (call["width"] + 1)
    r, c = call["rows"], call["cands"]
    j, t, n = call["tests"], call["width"], call["n"]
    ops = r * c * (j + t)
    byts = 4 * (r * t + c + min(r * j, n) * c + r)
    return ops, byts
