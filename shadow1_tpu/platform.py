"""Backend bootstrap: which platform a process runs on, said once.

JAX picks the platform the way JAX does — ``JAX_PLATFORMS`` when set, else
the accelerator — and a backend that fails to initialise ends the process
with JAX's own exception. Nothing here probes, retries or switches platform
on its own: a run that lands on a different device than the one asked for
must fail, not finish under the wrong label.

Two helpers remain:

* ``force_cpu(n)`` for the paths that must NOT take the accelerator
  (``--engine cpu``, the oracle-only tools, parents that run a comparator
  between children that need the chip). Call it before the first jax
  array/device operation; after backend init the platform is fixed.
* ``describe()`` — the three fields every result row carries so a reader
  can tell a chip run from a CPU run.

One process holds a chip at a time: a parent that has initialised a backend
blocks every child that needs the device. ``assert_backend_untouched`` is
the guard supervisors and clients call on their way out.
"""

from __future__ import annotations

import os
import re


def force_cpu(n_devices: int = 1) -> None:
    """Force the CPU platform with at least ``n_devices`` virtual devices.

    Must run before jax initializes a backend. XLA_FLAGS is read at CPU
    client creation, so mutating it here (pre-init) is effective. An
    existing ``--xla_force_host_platform_device_count`` smaller than
    ``n_devices`` is raised to ``n_devices``.
    """
    if n_devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            )
        elif int(m.group(1)) < n_devices:
            os.environ["XLA_FLAGS"] = (
                flags[: m.start()]
                + f"--xla_force_host_platform_device_count={n_devices}"
                + flags[m.end():]
            )
    import jax

    jax.config.update("jax_platforms", "cpu")


def describe() -> dict:
    """``{platform, device_kind, n_devices}`` as jax reports them.

    Initialises the backend if nothing has yet — call it from the process
    that runs the engine, never from a parent that only supervises."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def assert_backend_untouched(who: str) -> None:
    """Raise if this process initialised a jax backend.

    For parents whose children need the chip (the ``--ckpt`` supervisor,
    ``submit``): holding the device here would make every child fail or
    hang, and only on a machine with a real accelerator."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{who} initialised a jax backend; it must stay off the device "
            "so that the child process it starts can take the chip")
