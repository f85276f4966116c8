"""Plain float32 references of the configurations' architectures.

A configuration names its reference in its ``reference`` key (a path
from the checkout's root), and ``bench.harness.cell.load_cell`` loads it
by path.  A reference imports nothing of the program and takes nothing
the program made: it takes the canonical per-layer weights as served
(the bf16 values the adapter drew, from ``canonical_layer`` and
``canonical_globals``) and runs in float32, every matrix product at
``Precision.HIGHEST``.  It provides:

- ``hidden(layers, globals_, cfg, seq, lower="")``: the last layer's
  rows for the token sequence ``seq``, one layer at a time from the
  iterable ``layers``; ``lower`` ("int8" or "fp8") rounds the weight
  matrices to that type first, for the controls;
- ``gaps_at(h, globals_, cfg, n, tokens)``: how far each of ``tokens``
  lies below the best logit of the row that predicts it (position
  ``n + j`` for ``tokens[j]``);
- ``firsts_at(h, globals_, cfg, n, m)``: the token each row predicting
  positions ``n .. n+m-1`` puts first.
"""
