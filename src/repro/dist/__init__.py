"""``repro.dist`` — mesh helpers shared by the stencil stack.

The stencil stack expresses decomposition declaratively (``dmp``
dialect) and lowers it once (``comm`` dialect → ``lax.ppermute`` under
``shard_map``).  What is left here is mesh plumbing around that path:

- ``sharding.factor_slot_mesh`` — a spatial mesh extended with a slot
  axis (``api.pooled_target``, ``tune.space``);
- ``sharding.reshard`` — state placed under a new mesh on an elastic
  restore (``resilience.driver``).
"""
