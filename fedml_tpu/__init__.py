"""fedml_tpu — a TPU-native federated learning framework.

A from-scratch re-design of the capabilities of FedML (forestnoobie/FedML,
reference layer map in SURVEY.md) for TPU hardware:

- The reference's message-passing round (MPI/gRPC/MQTT point-to-point sends,
  ``fedml_core/distributed/communication/``) becomes ONE SPMD program over a
  ``jax.sharding.Mesh``: local client training is a jitted/`shard_map`-ped
  train step, aggregation is a weighted ``jax.lax.psum`` over ICI.
- The reference's per-process ClientManager/ServerManager/Trainer machinery
  (``fedml_core/distributed/{client,server}/``) becomes a thin host-side
  round driver around jitted collectives.
- Models are flax.linen modules (reference: torch.nn, ``fedml_api/model/``),
  optimizers are optax, checkpointing is orbax.

Subpackages
-----------
mesh        device mesh + sharding helpers                    (L0)
collectives tested collective wrappers (on-TPU "comm backend") (L1)
comm        cross-process transports: loopback | gRPC | MQTT,
            Message/Observer/manager pattern                  (L1)
core        client state, local update, round engine, sampler,
            partitioner, robust aggregation, topology,
            checkpointing, schedules                          (L2)
models      flax model zoo (+ sync-BN, norm-free ResNet)      (L3a)
data        partitioned dataset loaders (8-tuple contract),
            vertical tabular, poisoning, augmentation         (L3b)
algorithms  FedAvg, FedOpt, FedProx, FedNova, hierarchical,
            decentralized, robust, FedDF, SplitNN, VFL,
            TurboAggregate, FedGKT, FedNAS, FedSeg            (L4)
distributed cross-process 6-file runtimes over ``comm``       (L4)
parallel    ring / Ulysses sequence parallelism
ops         Pallas TPU kernels (flash attention)
native      C++ host data plane (ctypes)
experiments unified CLI + multi-process launcher              (L5)
utils       pytree ops, metrics, condensation
"""

__version__ = "0.1.0"
