"""Share of the traced window in which the device sat idle while the host
was in the session's own code: ``milo.build`` outside the preprocessor, and
``milo.fingerprint`` (the copy and SHA-256 of the feature matrix)."""
from bench import spans


def read(run: dict) -> float | None:
    return spans.idle_share(spans.for_run(run), spans.SESSION)
