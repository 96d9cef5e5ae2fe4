"""Layer: input.  Mean time per step that ``fit`` spent inside ``next()``
of the iterator it was handed, over the whole measured window (host clock
in the benchmark's own wrapper around the program's feed)."""


def read(facts):
    waits = facts["window"]["waits"]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
