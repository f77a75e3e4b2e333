"""How the `.cca` readers find the grouped-matmul kernels of their cell: data
that the readers under layer_metrics/ share.

The cell's experts are SQUARE (2048 <-> 2048), so benchmark/moe_faces.py's
shapes cannot tell the forward call (rows bf16[r, 2048] x matrices bf16[16,
2048, 2048]) from the transposed one that gives dx (the same shapes: that
file's back-reference needs two widths that differ).  What does tell them
apart is the kernel's own name, which `pl.pallas_call(name=)` leaves in the
instruction's `op_name` (ops/grouped_matmul.py) and the program's report
carries for every instruction a trace can show (`programs["train.step"]` in
timeline.json; benchmark/part_lib.py joins it to the trace): the patterns
below are searched in `op_name`, among the step module's Mosaic custom
calls.  The forward runs in the forward pass and again under remat; both are
`grouped_matmul`.

The flash forward's face is benchmark/swa_moe_faces.py's FORWARD_FULL (the
causal call's scalars, s32[2]): the cell has no windowed call."""
GROUPED_FORWARD = r"/grouped_matmul(?:/|$)"
GROUPED_TRANSPOSED = r"/grouped_matmul_t(?:/|$)"
GROUPED_DW = r"/grouped_matmul_dw(?:/|$)"
