"""deepdfa_tpu — a TPU-native dataflow-analysis-guided vulnerability-detection framework.

Brand-new implementation of the capabilities of aidanby/DeepDFA (ICSE'24 line of
work), designed for TPUs: JAX/XLA for compute, GSPMD/`jax.sharding` for scale,
Flax for modules, a host-side columnar CPG pipeline, and a C++ worklist solver
for exact reaching definitions.

Layer map (ours; reference layers cited in each module's docstring):

- :mod:`deepdfa_tpu.utils`     — storage layout, hashing, parallel map, seeding.
- :mod:`deepdfa_tpu.config`    — typed configuration (replaces the reference's
  feat-string DSL + layered YAML; see ``DDFA/code_gnn/main_cli.py:73-99``).
- :mod:`deepdfa_tpu.cpg`       — code-property-graph toolchain: Joern JSON
  ingestion, a native pycparser-based C frontend, reaching-definitions solvers.
- :mod:`deepdfa_tpu.data`      — datasets, vocab building, graph batching into
  fixed-shape padded :class:`~deepdfa_tpu.data.graphs.BatchedGraphs`.
- :mod:`deepdfa_tpu.models`    — Flax GGNN, fusion heads, Llama-family LLM.
- :mod:`deepdfa_tpu.ops`       — segment ops, differentiable set-union ops,
  attention (incl. ring attention), Pallas kernels.
- :mod:`deepdfa_tpu.parallel`  — mesh construction, sharding rules, collectives.
- :mod:`deepdfa_tpu.train`     — train loops, metrics, checkpoints, profiling.
"""

__version__ = "0.1.0"

import os as _os

# pandas 3 backs ``str`` columns with pyarrow, and with Arrow's default
# allocator (mimalloc) ``pa.array`` segfaults when the extraction / encode
# pool THREADS build string Indexes in a process that has also loaded jaxlib:
# the extraction suite run alone died 5 of 9 times on an 8-core host, 0 of 6
# with the system allocator. Arrow picks its pool when it is first loaded, so
# the choice is made here, before anything in the package imports pandas;
# an operator's own setting wins.
_os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")

from deepdfa_tpu.utils import (  # noqa: F401
    cache_dir,
    dfmp,
    external_dir,
    get_run_id,
    hashstr,
    processed_dir,
    seed_all,
    storage_dir,
)
