"""Plain reference for ``pythia-1.4b`` (all 24 layers).

The same publication and the same mathematics as ``pythia-1.4b-d6``:
the two configurations differ in depth and in how the system lays the
model out over chips, and a plain reference has no layout.  So this file
takes ``logits``, ``loss`` and the AdamW step from that one.

One thing differs, and it is not mathematics: for the gradient the
layers run as a ``lax.scan`` over the stacked parameters with each layer
under ``jax.checkpoint``, so that the float32 backward pass of one
2048-token sequence keeps one layer's activations (about 1 GB) and not
24 layers' (about 17 GB).  The reference reads the parameters where the
driver made them (spread over the cell's chips, 1.4 GB each), and its
gradients and updated copy stay there (``adamw_step`` in the d6 file).
"""

import functools
import os

import jax

from benchmarks.lib.common import load_module

_d6 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "pythia-1.4b-d6.py"), "ref_pythia_d6")
logits = _d6.logits
loss = _d6.loss


def _forward(params, ids, c):
    """``_d6._forward`` with the layers scanned and recomputed."""
    layer = jax.checkpoint(lambda x, lp: _d6._layer(x, lp, c))
    x = params["embed"]["table"].astype(_d6.F32)[ids]
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x,
                        params["blocks"])
    return _d6._logits_from(x, params, c)


adamw_step = functools.partial(_d6.adamw_step, forward=_forward)
