"""Share of the traced window in which no operation ran on the device,
over the selection cells' artifact builds."""


def read(run: dict) -> float | None:
    t = run["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
