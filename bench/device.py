"""The chip a run holds: the check for it, its peaks, memory and compiles."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class UnknownDeviceError(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; raises ``NoChipError`` otherwise.

    There is no fallback to the CPU: a number from another platform is never
    reported under a device metric's name.
    """
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(f"the benchmark needs a TPU; JAX found platform "
                          f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChipError(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    table = table if table is not None else json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise UnknownDeviceError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def memory(dev) -> tuple[int, int]:
    """(peak bytes in use, bytes limit) of one device."""
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_limit", 0))


class CompileClock:
    """Backend compile seconds and count, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1
