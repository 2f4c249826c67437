"""The device a run is on: the chip check, the peaks table, peak memory."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind the benchmark measures."""


def check_devices(chips: int, rehearsal: bool = False) -> list:
    """The devices a cell runs on. Outside a rehearsal they must be TPUs,
    at least ``chips`` of them; anything else raises :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if rehearsal:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise NoChip(f"the benchmark measures a TPU; JAX found "
                     f"{devs[0].platform} ({len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str, path: Path = PEAKS_FILE) -> dict:
    """Peaks of one ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)} ({path})")
    return table[kind]


def memory_peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    keeps no statistics, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
