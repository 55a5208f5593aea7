"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding
(`quickwit_tpu.parallel`) is exercised without TPU hardware. Both settings
must be made before any backend initializes.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
