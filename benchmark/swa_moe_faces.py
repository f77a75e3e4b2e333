"""The faces of the flash-attention kernels in a trace of the `.swamoe`
cell: data that its readers under layer_metrics/ share.  A Pallas kernel has
no name of its own there: it is a custom-call whose HLO line gives result
and operands (ops/attention.py).

The forward returns (out bf16[heads, seq, d], log-sum-exp f32[heads, 8,
seq]); its first operand is the prefetched scalars: [q_off, kv_off] of a
causal call, s32[2], and [q_off, kv_off, window] of a windowed one, s32[3]
(`_chunk`), which is how a sliding layer's call is told from a full
layer's whatever their head counts; q follows.  The backward, one call,
returns (dq, dk, dv), three bf16 arrays, behind the same scalars.  The rope
tables a call takes come last and change no face.  The grouped-matmul
kernels' faces are benchmark/moe_faces.py's: the cell's two widths differ
(3072, 1024), as that file's back-reference needs."""
_OUT = r"bf16\[\d+,\d+,\d+\]"
FORWARD_WINDOWED = (r"= \(" + _OUT + r", f32\[\d+,8,\d+\]\) "
                    r"custom-call\(s32\[3\] ")
FORWARD_FULL = (r"= \(" + _OUT + r", f32\[\d+,8,\d+\]\) "
                r"custom-call\(s32\[2\] ")
BACKWARD = (r"= \(" + _OUT + ", " + _OUT + ", " + _OUT + r"\) "
            r"custom-call\(s32\[[23]\] ")
