"""repro: X-PEFT multi-profile training/serving framework in JAX."""
import jax as _jax

# Sharding-invariant RNG, process-wide: with the legacy (non-partitionable)
# threefry lowering, a jax.random draw whose consumer is GSPMD-sharded
# produces DIFFERENT values than the same draw unsharded — the gang step's
# Gumbel mask noise would silently diverge between 1 device and a mesh.
# Partitionable threefry makes every draw a pure function of (key, element
# index) regardless of partitioning, which the multi-device parity gate
# (benchmarks/sharded_smoke.py) relies on. It is the installed JAX's
# default; pinning it here keeps a host that flips the flag from breaking
# bitwise parity silently.
_jax.config.update("jax_threefry_partitionable", True)

__version__ = "1.0.0"
