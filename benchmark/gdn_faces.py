"""The faces of the gated-delta-rule kernels and of the head-256 flash
forward in a trace of the `.gdn` cell: data that its readers under
layer_metrics/ share.  A Pallas kernel has no name of its own there: it is a
custom-call whose HLO line gives result and operands (ops/gated_delta.py,
ops/attention.py).

The rule's forward takes q and k as their projection lays them (bf16[b, t,
key heads x d_k]), v (bf16[b, t, value heads x d_v]) and the chunks' running
sums and beta (f32[b x heads, chunks, 64]), and returns o like v, alone (the
primal pass) or with the state every block of chunks starts from (f32[b x
heads, blocks, d_k, d_v]: under differentiation, for the backward).  The
backward returns five arrays: dq and dk a value head, dv, dG and dbeta.  No
other kernel of the program has a bf16 3-D array as its FIRST operand (the
flash and grouped kernels begin with their prefetched scalars).

The full layers' flash forward is the causal call's face (results bf16[b, s,
heads x 256] and f32[b x heads, 8, s] behind s32[2]): the cell has no
windowed call.  The grouped-matmul kernels' faces are benchmark/
moe_faces.py's: the cell's two widths differ (2048, 512), as that file's
back-reference needs."""
_SEQ = r"bf16\[\d+,\d+,\d+\]"
_ROWS = r"f32\[\d+,\d+,\d+\]"
_FIRST_OPERAND = r" custom-call\(" + _SEQ + " "
# The forward is COUNTED (a call's least time x the calls found), so its
# pattern reads on to the fifth operand: no further event of a trace looks
# like that (read only to the first operand, 148 events of a traced run of
# four steps matched where 24 calls ran, their time the 24 calls'; my chip
# run, PR 42).  The backward's five results fill most of the 256 characters
# a profile keeps of a name, so its pattern reads no further than the first
# operand; only its time is read.
RULE_FORWARD = (r"= (" + _SEQ + r"|\(" + _SEQ + r", f32\[\d+,\d+,\d+,\d+\]\))"
                + _FIRST_OPERAND + "[^,]+, " + _SEQ + " [^,]+, " + _SEQ
                + " [^,]+, " + _ROWS + " [^,]+, " + _ROWS + " ")
RULE_BACKWARD = (r"= \(" + _SEQ + ", " + _SEQ + ", " + _SEQ + ", " + _ROWS
                 + ", " + _ROWS + r"\)" + _FIRST_OPERAND)
FLASH_FORWARD = (r"= \(" + _SEQ + r", f32\[\d+,8,\d+\]\) "
                 r"custom-call\(s32\[2\] ")
