"""``__graft_entry__.entry()`` must be jit-lowerable."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_graft_entry_lowers():
    import jax

    sys.path.insert(0, REPO)
    import __graft_entry__ as g

    fn, args = g.entry()
    jax.jit(fn, donate_argnums=(1,)).lower(*args)
