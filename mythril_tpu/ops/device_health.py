"""Which device this process computes on, and whether it still does.

One in-process answer, read once and cached: ``jax.default_backend()``
after :func:`mythril_tpu.ops.configure_jax` (JAX honours
``JAX_PLATFORMS`` by itself).  A backend that fails to initialise
raises from :func:`backend_name`; nothing picks the CPU or Pallas
interpret mode because a device query failed.

The answer changes at most once per process, from "on the device" to
"demoted": the dispatch watchdog's last rung calls
:func:`mark_unhealthy` when a failing dispatch's own error says the
device is gone (``resilience/watchdog.py``), and the fault plane's
``probe_flap`` point does the same in tests.  Every later device path
then leaves its work to the native CDCL, counts the skip in
``unhealthy_skips`` (:func:`count_skip`) and names the demotion in a
warning.  Results are unchanged; only the device speedup is lost.
"""

import logging
import threading
from typing import Optional

log = logging.getLogger(__name__)

_lock = threading.Lock()
_backend: Optional[str] = None
_demoted: Optional[str] = None  # why the process left the device
_warned_sites: set = set()


def backend_name() -> str:
    """The default JAX backend ('tpu', 'cpu', ...), read once.  Raises
    what JAX raises when the backend cannot initialise."""
    global _backend
    if _backend is None:
        from mythril_tpu.ops import configure_jax

        configure_jax()
        import jax

        _backend = jax.default_backend()
    return _backend


def mark_unhealthy(reason: str) -> None:
    """Demote the whole process for the rest of the run: every later
    device path degrades through ``unhealthy_skips``."""
    global _demoted
    with _lock:
        if _demoted is not None:
            return
        _demoted = reason
    log.warning("device demoted for the rest of the run: %s", reason)


def demotion() -> Optional[str]:
    """Why the process was demoted, or None while it is on the device.
    Unlike :func:`device_ok` this fires no fault point."""
    return _demoted


def device_ok() -> bool:
    """False once the process was demoted (never flips back)."""
    from mythril_tpu.resilience import faults

    if faults.health_flap():
        mark_unhealthy("injected probe flap")
    return _demoted is None


def count_skip(site: str) -> None:
    """Count one device path skipped on a demoted process; the first
    skip of each site names the demotion in a warning."""
    from mythril_tpu.ops.batched_sat import dispatch_stats

    dispatch_stats.unhealthy_skips += 1
    if site not in _warned_sites:
        _warned_sites.add(site)
        log.warning(
            "%s skipped on the demoted device (%s); later skips are "
            "counted in unhealthy_skips", site, _demoted,
        )


def reset_for_tests() -> None:
    global _backend, _demoted
    _backend = None
    _demoted = None
    _warned_sites.clear()
