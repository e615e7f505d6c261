"""Faults planted under the timed path, to show that ``correct`` catches
them. Each is a context manager around a built run (or, for the
exchange, around the program's gather), and is used only by the
calibration (``calibrate.py``) and the tests, never by a benchmark run.

- ``unchanged_state``: every step returns the parameters and optimizer
  state it was given (it still reports its loss).
- ``half_batch``: half of the batch is left out of the loss and the
  gradient, the mean taken over the rest: the last half of the rows, or
  of the positions where the batch has a single row.
- ``no_exchange``: the gradient of every trainable parameter's gather
  skips the reduce-scatter between chips: each chip keeps only its own
  share of the gradient of its own rows.
"""
from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def unchanged_state(st):
    step = st.do_train_step

    def keep(batch):
        train_p = [x.copy() for x in st.train_p]
        opt = jax.tree.map(lambda x: x.copy(), st.opt)
        m = step(batch)
        st.train_p, st.opt = train_p, opt
        return m
    st.do_train_step = keep
    try:
        yield
    finally:
        st.do_train_step = step


class _HalfMask:
    def __init__(self, ds):
        self.ds = ds

    def batch_np(self, step):
        b = dict(self.ds.batch_np(step))
        mask = b["mask"].copy()
        B, S = mask.shape
        if B > 1:
            mask[B // 2:] = False
        else:
            mask[:, S // 2:] = False
        b["mask"] = mask
        return b


@contextlib.contextmanager
def half_batch(st):
    ds = st.loader.ds
    st.loader.ds = _HalfMask(ds)
    try:
        yield
    finally:
        st.loader.ds = ds


def _gather_without_reduce(axes, axis):
    """A tiled all-gather whose transpose keeps this chip's slice of the
    cotangent instead of summing it over the chips."""
    @jax.custom_vjp
    def ag(x):
        return jax.lax.all_gather(x, axes, axis=axis, tiled=True)

    def fwd(x):
        return ag(x), x.shape[axis]

    def bwd(n, g):
        idx = jax.lax.axis_index(axes)
        return (jax.lax.dynamic_slice_in_dim(g, idx * n, n, axis),)

    ag.defvjp(fwd, bwd)
    return ag


@contextlib.contextmanager
def no_exchange(st=None):
    """Planted in the program's gather, so it must be active when the
    step is first traced."""
    from repro.core import fcdp
    from repro.core.residency import residency_of
    original = fcdp._ag_fn

    def ag_fn(plan):
        if residency_of(plan).invariant_gather:
            return original(plan)
        return lambda x, axes, axis: _gather_without_reduce(
            tuple(axes), axis)(x)
    fcdp._ag_fn = ag_fn
    try:
        yield
    finally:
        fcdp._ag_fn = original


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange}
