"""raft_tpu — a TPU-native library of ML/IR primitives and vector-search
algorithms with the capabilities of RAPIDS RAFT (reference: shrshi/raft
24.08), re-designed for JAX/XLA/Pallas on TPU device meshes.

Layer map (bottom-up; see SURVEY.md):

* ``raft_tpu.core``      — resources, errors, logging, serialize, bitsets,
                           interruptible (L1).
* ``raft_tpu.utils``     — tiling/alignment math (L2 concepts).
* ``raft_tpu.ops``       — primitives: pairwise distance, select_k, fused
                           1-NN, linalg, matrix ops (L4).
* ``raft_tpu.random``    — counter-based RNG, data generators (L4).
* ``raft_tpu.stats``     — descriptive stats + model/ANN metrics (L4).
* ``raft_tpu.sparse``    — COO/CSR ops, sparse distances, MST, Lanczos (L4/L5).
* ``raft_tpu.cluster``   — kmeans, balanced kmeans, single-linkage (L5).
* ``raft_tpu.neighbors`` — brute-force, IVF-Flat, IVF-PQ, CAGRA, NN-descent,
                           refine, filters (L5).
* ``raft_tpu.parallel``  — mesh comms (collectives verb set), sharded
                           build/search (L3).
* ``raft_tpu.bench``     — ann-benchmarks-style harness (L8).
"""

__version__ = "0.1.0"

from raft_tpu.core import Resources, default_resources

__all__ = ["Resources", "default_resources", "__version__"]
