"""Work of the cni_update index kernel, counted from the job's shapes.

One call re-encodes ``rows`` frontier vertices over ``labels`` label
columns: it adds each (vertex, label) count delta and takes the descending
prefix over the labels (two operations per entry), reads the count rows
and deltas, and writes the new rows, one log digest and one degree per
vertex.  The kernel's lane-select over the (d_max, max_p) table is how it
is done, not the job, and is not counted.  Values are 4 bytes.
"""

PROGRAMS = ("cni_update",)


def work(call: dict) -> tuple[int, int]:
    f, lab = call["rows"], call["labels"]
    return 2 * f * lab, 4 * (3 * f * lab + 2 * f)
