"""Backend compiles inside the measured window (JAX monitoring events).
Set-up warms every shape the cell uses, so this should read 0."""


def read(run: dict) -> float | None:
    n = run.get("window_compiles")
    return None if n is None else float(n)
