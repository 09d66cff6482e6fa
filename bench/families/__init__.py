"""Model families: one file each, named by a configuration's ``family``.

A family file gives the params and cache layout the program's language
model reads for that family, and the initialisation of its weights:

* ``param_shapes(cfg)``: the params tree, nested dicts of leaf shapes;
* ``cache_shapes(cfg, slots, max_seq)``: ``name -> (shape, dtype name)``
  of every cache leaf of a ``slots x max_seq`` ServeState;
* ``init_leaf(path, shape, key, cfg)``: the float32 value of the params
  leaf at the dotted ``path``.

The harness loads it by path (``harness.load_family``), so a new family
is a new file here.
"""
