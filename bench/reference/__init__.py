"""Plain reference of chromatic Gibbs sampling with LUT-exp weights and
rejection Knuth-Yao draws, written from the configuration's stated datapath
in straightforward `jax.numpy`.  It imports nothing of the program and uses
none of its tables: colorings, CPT arenas and LUTs are built here.

Draws are a deterministic function of the seed, so the reference reproduces
every served bit: final chain states and marginal histograms are compared
exactly.  Modules are named after the configuration's `reference` key.
"""
