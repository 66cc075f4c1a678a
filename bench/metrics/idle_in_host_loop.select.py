"""Share of the traced window in which the device sat idle while the host
was in the preprocessor: ``milo.preprocess`` and every span below it (the
per-partition slices and copies, Gram and engine dispatches, fetches,
Taylor-softmax and the merge)."""
from bench import spans


def read(run: dict) -> float | None:
    return spans.idle_share(spans.for_run(run), spans.HOST_LOOP)
