"""Blocking device-to-host reads (``milo.fetch`` spans) per artifact,
averaged over the ``milo.build`` spans that lie whole inside the traced
window."""
from bench import spans


def read(run: dict) -> float | None:
    red = spans.for_run(run)
    if red is None:
        return None
    return sum(b["fetches"] for b in red["builds"]) / len(red["builds"])
