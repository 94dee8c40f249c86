"""The shared stencil compiler core: the SSA IR (``ir``), its dialects
(``stencil``, ``dmp``, ``comm``), the passes over them and the lowering
to JAX."""
