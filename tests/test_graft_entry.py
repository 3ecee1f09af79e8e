"""Keep the driver entry points green on the CPU mesh."""

import os
import sys

import jax


def test_entry_jittable():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2 and out.ndim == 3
    assert bool(jax.numpy.isfinite(out).all())


def test_dryrun_multichip_8():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    g.dryrun_multichip(8)
