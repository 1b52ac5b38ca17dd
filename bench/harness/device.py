"""The accelerator a run is on. A run without one refuses to report."""
from __future__ import annotations


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(f"JAX found no accelerator (platform "
                            f"{devs[0].platform}, {devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
