"""Central jax import + configuration.

Every device-side module imports jax through here so that 64-bit integer
support is enabled exactly once, before any tracing happens.  The analyzer's
accumulators are genuinely 64-bit (byte sums over billions of records exceed
2^32; the reference uses ``u64`` throughout, src/metric.rs:12-26), so we
enable ``jax_enable_x64`` globally.  Per-record *contributions* stay int32
where possible to keep the TPU hot path cheap; only the accumulator state is
64-bit.

The platform is JAX's own choice (``JAX_PLATFORMS`` in the environment);
nothing here probes, forces or falls back.
"""

import os

import jax

from kafka_topic_analyzer_tpu.obs import metrics as obs_metrics

jax.config.update("jax_enable_x64", True)

#: The persistent compilation cache used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: one fixed directory inside the checkout (the path is part of
#: the cache key, so it never moves).  When the variable is set, JAX's own
#: setting stands and no other directory is set here.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)

obs_metrics.install_program_build_listener()


def require_device_platform(device) -> None:
    """Refuse a device path that JAX put on the host by itself.

    When no accelerator starts, JAX falls back to the CPU with only a
    warning; a ``--backend tpu`` scan would then run on the host and still
    exit 0.  The CPU is accepted only when ``JAX_PLATFORMS`` asks for it
    (tests, virtual meshes, deliberate host runs)."""
    if device.platform != "cpu":
        return
    requested = {
        p.strip() for p in (jax.config.jax_platforms or "").split(",")
    }
    if "cpu" not in requested:
        raise RuntimeError(
            "--backend tpu found no accelerator: JAX fell back to the cpu "
            "platform.  Set JAX_PLATFORMS=cpu to run the device path on the "
            "host deliberately."
        )


import jax.numpy as jnp  # noqa: E402,F401
from jax import lax  # noqa: E402,F401
