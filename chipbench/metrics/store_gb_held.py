"""GB the store nodes hold in memory when the window closes: the program's
wall counters ``store_bytes_in`` (puts, fetches, ingests) less
``store_bytes_dropped`` (retention, gc), over every node. Traced runs switch
the wall on; where the program has no such counters there is nothing to
read."""


def read(run):
    held = getattr(run, "store_bytes_held", None)
    return None if held is None else held / 1e9
