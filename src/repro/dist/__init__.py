"""repro.dist — placement for the LM stack (models, train, serve).

  sharding     — declarative PartitionSpec rules (FSDP×TP layouts,
                 divisibility guards, batch/cache specs, mesh modes)
  compression  — int8 error-feedback gradient allreduce for the
                 slow (cross-pod / DCN-class) links

Model/optimizer code never names a device: it receives a ``ShardCfg`` and
spec trees built here, and the same numerics run single-device (mesh=None)
or across a pod slice unchanged.  The CFD framework places its grids and
farm slots itself (``core.driver``, ``sim.ensemble``) and imports nothing
from this package.
"""
