"""``tree_device_s`` for a cell on the mesh: the same reading (the tree
modules' device seconds on the first chip), under a name of its own because
there it should move the wall-clock ``train_s``, which only the four-chip
machine holds steady; on one chip ``tree_device_s`` moves
``train_device_s``.  A metric names one metric that it moves.
"""
from perfbench.metrics import tree_device_s

LAYER = tree_device_s.LAYER
UNIT = tree_device_s.UNIT
MOVES = "train_s"
read = tree_device_s.read
