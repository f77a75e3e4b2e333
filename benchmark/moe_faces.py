"""The faces of the grouped-matmul kernels and of the latent-attention flash
forward in a trace: data that the `.moe` readers under layer_metrics/ share.
A Pallas kernel has no name of its own there: it is a custom-call whose HLO
line gives result and operands (ops/grouped_matmul.py, ops/attention.py).

Grouped matmul: two prefetched scalars first (the tiles' groups s32[tiles],
the tiles used s32[1]), then the rows bf16[rows, k] and
  - forward: the matrices bf16[groups, k, n] (their middle size is the
    rows' width), one result bf16[rows, n];
  - transposed (dx): the matrices bf16[groups, n, k] (their LAST size is
    the rows' width), one result bf16[rows, n];
  - dw: a second array of rows bf16[rows, n], one result bf16[groups, k, n].
The cell's two widths differ (2048, 768), so a back-reference tells forward
from transposed.

Latent attention's forward: the flash forward's face (results bf16[bh, s,
128] and f32[bh, 8, s] behind s32[2]) with q bf16[bh, s, 192] as its second
operand: keys wider than values."""
_SCALARS = r"custom-call\(s32\[\d+\] [^,]+, s32\[1\] [^,]+, "
_ROWS = r"bf16\[\d+,(\d+)\] [^,]+, "
GROUPED_FORWARD = (r"= bf16\[\d+,\d+\] " + _SCALARS + _ROWS
                   + r"bf16\[\d+,\1,\d+\] ")
GROUPED_TRANSPOSED = (r"= bf16\[\d+,\d+\] " + _SCALARS + _ROWS
                      + r"bf16\[\d+,(?!\1,)\d+,\1\] ")
GROUPED_DW = (r"= bf16\[\d+,\d+,\d+\] " + _SCALARS
              + r"bf16\[\d+,\d+\] [^,]+, bf16\[\d+,\d+\] ")
MLA_FORWARD = (r"= \(bf16\[\d+,\d+,128\], f32\[\d+,8,\d+\]\) "
               r"custom-call\(s32\[2\] [^,]+, bf16\[\d+,\d+,192\] ")
