"""The one JAX symbol this repo takes from a private module.

The repo targets JAX 0.9: call sites use ``jax.shard_map``,
``jax.typeof``, ``jax.lax.axis_size`` and ``jax.lax.pcast`` directly.
The invariant (replicated-typed) all-gather is not exported publicly in
0.9, so it is imported here, once, from ``jax._src``. Its output is
typed replicated over the gathered axis, which the frozen-parameter
gathers and the hier strategy's widened updated-shard gather need to
satisfy their shard_map out_specs.
"""
from jax._src.lax.parallel import all_gather_invariant  # noqa: F401
