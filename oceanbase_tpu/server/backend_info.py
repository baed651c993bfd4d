"""Resolved-backend identity: which hardware is this process ACTUALLY on.

One authoritative shape for the resolved backend, reused by:

- the Database boot log line (one line per boot, INFO level);
- the ``gv$backend`` virtual table (the same facts through SQL);
- ``scripts/sf_parity.py`` / ``scripts/profile_bench.py`` artifact
  tagging, so a JSON line carries its own provenance.
"""

from __future__ import annotations

import os


def resolve_backend() -> dict:
    """-> {platform, device_kind, device_count, cpu_fallback} of the
    live jax backend; degrades to an 'unavailable' row rather than
    raising (the virtual table must stay readable mid-outage)."""
    try:
        import jax

        devs = jax.devices()
        platform = devs[0].platform if devs else "unknown"
        kind = str(getattr(devs[0], "device_kind", "")) if devs else ""
        count = len(devs)
    except Exception as e:  # noqa: BLE001 — a backend that fails to
        # initialize must not take the observability plane down with it
        return {"platform": "unavailable", "device_kind": str(e)[:80],
                "device_count": 0, "cpu_fallback": True}
    # cpu_fallback: JAX_PLATFORMS asked for tpu but the resolved
    # platform is cpu
    wanted_tpu = "tpu" in os.environ.get("JAX_PLATFORMS", "").lower()
    return {"platform": platform, "device_kind": kind,
            "device_count": count,
            "cpu_fallback": platform == "cpu" and wanted_tpu}


def backend_summary(units=None) -> str:
    """One-line boot summary: backend kind, device count, calibration
    age."""
    b = resolve_backend()
    age = units.age_s() if units is not None else -1.0
    bits = [
        f"platform={b['platform']}",
        f"device_kind={b['device_kind'] or '-'}",
        f"devices={b['device_count']}",
        f"cpu_fallback={int(b['cpu_fallback'])}",
        "calibration_age_s="
        + (f"{age:.0f}" if age >= 0 else "uncalibrated"),
    ]
    return " ".join(bits)
