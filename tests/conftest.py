"""Shared test configuration.

The suite runs on the CPU: ``JAX_PLATFORMS`` defaults to ``cpu`` here,
before JAX is imported, so a machine with an accelerator does not hand it
to the test workers.  The chip is reached only through ``chip_smoke.py``.

float64 is enabled globally so the JAX job model matches the pure-Python
float64 oracle bit-for-bit in the equivalence property tests; neural-net
code paths pin their own dtypes explicitly and are unaffected.

NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here — tests
and benches must see the single real CPU device.  Only ``launch/dryrun.py``
forces 512 placeholder devices, in its own process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
