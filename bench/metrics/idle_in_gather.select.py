"""Share of the traced window in which the device sat idle while the host
gathered a chunk's own rows (``milo.gather``): where a bucket group is split
into chunks, each reads fewer than all rows and copies them out of the
feature matrix before its put.  None where no idle lies under a
``milo.gather`` span, as in a trace without one (a program that has no such
span, or a cell whose chunks read every row)."""
from bench import spans

GATHER = "milo.gather"


def read(run: dict) -> float | None:
    red = spans.for_run(run)
    if red is None:
        return None
    ns = [v for k, v in red["idle_ns"].items() if k.split("/")[-1] == GATHER]
    if not ns:
        return None
    return 100.0 * sum(ns) / (red["devices"] * red["window_s"] * 1e9)
