"""Select the JAX platform explicitly (dev-on-CPU vs serve-on-TPU) and say
which one a process really got.

SURVEY.md section 4 calls for a CPU backend so the serving path is testable
without TPUs.  ``JAX_PLATFORMS`` (or ``--platform`` / ``$KDLT_PLATFORM``,
which set the same jax config) selects it; nothing here falls back: a
process asked for a platform either runs on it or fails at start-up.
"""

from __future__ import annotations

import os

PLATFORM_ENV = "KDLT_PLATFORM"


def force_virtual_cpu(n_devices: int) -> None:
    """Re-point a process at an n-device virtual CPU mesh, even if a backend
    has already been initialized.

    ``--xla_force_host_platform_device_count`` is parsed from $XLA_FLAGS once
    per process by XLA's C++ flag parser, so it cannot help after any backend
    init; instead this clears jax's backend caches and uses the
    ``jax_num_cpu_devices`` config, which is read at (re-)creation of the CPU
    client.  Used by the driver's ``dryrun_multichip`` entry, which may run
    in a process that already compiled ``entry()`` on one device.
    """
    import jax

    force_platform("cpu")
    try:
        import jax._src.xla_bridge as xb

        xb._clear_backends()
        if hasattr(xb.get_backend, "cache_clear"):
            xb.get_backend.cache_clear()
        jax.config.update("jax_num_cpu_devices", n_devices)
    except Exception as e:  # pragma: no cover - depends on jax internals
        raise RuntimeError(
            "force_virtual_cpu could not rebuild the CPU backend with "
            f"{n_devices} devices (jax {jax.__version__} internals changed?). "
            "Start the process with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "before any jax import instead."
        ) from e


def force_platform(name: str | None) -> str | None:
    """name: "cpu", "tpu", ... or None => honor $KDLT_PLATFORM, else default.

    Returns the platform that was requested (None = whatever JAX finds).
    Must run before the first backend touch.
    """
    if name is None:
        name = os.environ.get(PLATFORM_ENV)
    if not name:
        return None
    import jax

    jax.config.update("jax_platforms", name)
    return name


def require_platform(requested: str | None) -> dict:
    """Initialise the backend and describe the device this process serves
    from: ``{"platform", "device_kind", "device_count"}``.

    With ``requested`` set, any other platform is fatal (RuntimeError): a
    server asked for a TPU must not come up healthy on the CPU.
    """
    import jax

    devices = jax.local_devices()
    found = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    if requested and found["platform"] != requested:
        raise RuntimeError(
            f"platform {requested!r} was requested but JAX found "
            f"{found['platform']!r} ({found['device_kind']}); refusing to "
            "start on a device that was not asked for"
        )
    return found
