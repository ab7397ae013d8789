"""Production mesh builder.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets the 512-placeholder-device
XLA flag *before* any jax initialization.
"""

from __future__ import annotations

import jax


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model_parallel: int = 1, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), axes,
                         axis_types=_auto(2))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_LINK_BW = 50e9              # B/s per link


def scan_devices(n: int | None = None) -> list:
    """Devices for the distributed scan executor (dataset/executor.py).

    ``None`` → every jax device.  ``n`` → the first n devices.  On an
    accelerator, asking for more devices than exist raises: a scan that
    reports ``devices=4`` ran on four chips.  Only the CPU backend cycles
    (so devices=4 still runs — and still reduces deterministically — in a
    one-device test process; real parallelism there needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count``).
    """
    devs = list(jax.devices())
    if n is None:
        return devs
    n = max(1, int(n))
    if n > len(devs) and jax.default_backend() != "cpu":
        raise ValueError(
            f"asked for {n} scan devices; the {jax.default_backend()} "
            f"backend has {len(devs)}")
    return [devs[i % len(devs)] for i in range(n)]
