"""repro: liquidSVM (Steinwart & Thomann, 2017) as a multi-pod JAX framework.

Layers:
  repro.api          staged train->select->test sessions, scenario
                     front-ends (mcSVM/lsSVM/qtSVM/exSVM/nplSVM/rocSVM),
                     string-key config layer
  repro.cli          `python -m repro.cli {train,select,test}` — the staged
                     cycle as separate processes over persisted artifacts
  repro.core         solvers + CV + selection (the paper's contribution)
  repro.cells        working-set decomposition (random/Voronoi/recursive/overlap)
  repro.tasks        OvA/AvA/NP/quantile task creation
  repro.data         synthetic data + scaling + LM token pipeline
  repro.distributed  mesh-aware cell sharding, compression, planner
  repro.kernels      Pallas TPU kernels (kernel_matrix, svm_predict,
                     flash_attention) with jnp oracles
  repro.models       assigned LM architectures (GQA/MoE/RWKV6/Mamba/hybrid)
  repro.train        optimizers, checkpointing, fault tolerance, loops
  repro.serve        KV cache + prefill/decode
  repro.configs      one config per assigned architecture
  repro.launch       mesh, multi-pod dry-run, train/serve drivers
"""
__version__ = "1.0.0"
