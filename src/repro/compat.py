"""The JAX symbols this repo takes from private modules.

The repo targets JAX 0.9: call sites use ``jax.shard_map``,
``jax.typeof``, ``jax.lax.axis_size`` and ``jax.lax.pcast`` directly.
The invariant (replicated-typed) all-gather is not exported publicly in
0.9, so it is imported here, once, from ``jax._src``. Its output is
typed replicated over the gathered axis, which the frozen-parameter
gathers and the hier strategy's widened updated-shard gather need to
satisfy their shard_map out_specs.

``check_vma`` is the switch ``jax.shard_map(check_vma=...)`` sets while
it traces its body. The Pallas kernels bundled with JAX build their
output shapes without varying-axes types, which a checked body refuses;
``kernels/ops.py`` turns the check off around such a call and types the
results itself (``check_vma(False)`` is a context manager). For that it
calls the bundled flash-attention kernel's own forward and backward
rules, ``flash_attention_fwd`` and ``flash_attention_bwd`` here, by
keyword (``tests/test_attention_kernel.py`` pins their parameters).
The wrapper and these three imports can go once the bundled kernel
types its outputs with varying axes.
"""
from jax._src.config import _check_vma as check_vma  # noqa: F401
from jax._src.lax.parallel import all_gather_invariant  # noqa: F401
from jax.experimental.pallas.ops.tpu.flash_attention import (  # noqa: F401
    _flash_attention_bwd as flash_attention_bwd,
    _flash_attention_fwd as flash_attention_fwd)
