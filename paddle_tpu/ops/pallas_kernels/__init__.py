"""Builder-written Pallas TPU kernels for ops where XLA's default lowering
underperforms (the role of the reference's hand-tuned ``operators/jit/`` —
7.2k LoC of JIT-assembled CPU kernels for hot ops).

Kernels:
- softmax_xent: fused softmax + cross-entropy over large vocab
  (forward never materializes the [N, V] probabilities in HBM).
- sparse_adam: row-wise sparse Adam/SGD update — batched dynamic-slice row
  DMA replacing the three ~30 GB/s XLA scatter fusions on SelectedRows
  embedding updates (benchmarks/SPARSE_PROFILE.md §1).
- expert_stream: the routed experts' gate, up and down products of a decode
  pass as ONE kernel that streams each touched expert's weights once
  (``ragged_dot`` x 3 reads 47-84% of that stream at the served shapes).
- short_attention: attention whose whole key length is one tile (S <= 512),
  forward and ONE backward kernel, dropout and segment ids inside: no
  ``[B, H, S, S]`` tensor reaches HBM (Transformer-base at S = 256).

Each kernel has an XLA-composed reference implementation it is numerically
tested against, and ``benchmarks/bench_softmax_xent.py`` /
``benchmarks/diag_sparse.py`` measure the win on real TPU hardware.
"""

from .softmax_xent import fused_softmax_xent, softmax_xent_supported  # noqa: F401
from .sparse_adam import (  # noqa: F401
    sparse_adam_rows,
    sparse_rows_gate,
    sparse_rows_supported,
    sparse_sgd_rows,
)
