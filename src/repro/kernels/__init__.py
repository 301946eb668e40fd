"""Pallas TPU kernels for the paper's compute hot-spots.

- dbl_query: fused label-verdict kernel (the ρ>95% query fast path)
- bfs_prune: fused admit-plane kernel feeding the pruned-BFS lanes

Both are validated against pure-jnp oracles (ref.py) in interpret mode on
the CPU, and compile for a TPU (tests/test_tpu_compile.py).  Every entry
point takes ``interpret`` explicitly: there is no default that could run
the interpreter on a chip.
"""
from .dbl_query.ops import query_verdicts  # noqa: F401
from .bfs_prune.ops import admit_plane  # noqa: F401
