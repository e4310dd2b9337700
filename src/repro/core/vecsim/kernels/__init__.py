"""Pallas delivery-sweep kernels for the vecsim hot path (DESIGN.md
§2.6) — kernel/ops/ref layout mirroring ``repro.kernels``:

  * ``kernel.py`` — the Pallas kernels (column-tiled grid, row-loop
    scatter-min);
  * ``ops.py``    — padding/dispatch wrappers and the availability
    probe (this module's public surface);
  * ``ref.py``    — plain-lax references each kernel unit-tests against.

Importing this package is cheap and jax-free; jax/pallas load on first
op call, and :func:`pallas_available` reports whether (and how) the
kernels can run here.
"""

from .ops import *  # noqa: F401,F403
from .ops import __all__  # noqa: F401
