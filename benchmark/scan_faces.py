"""The faces of the selective-scan kernels in a trace: data that the two
scan readers under layer_metrics/ share.  A Pallas
kernel has no name of its own there: it is a custom-call whose HLO line
gives result and operands (ops/selective_scan.py).  The forward returns y
f32[rows, time, blocks, 8, 128], alone (the primal pass) or with the
chunks' first states f32[rows, chunks, blocks, state, 8, 128] (under
remat, for the backward); its first operand is x in the same 5-D view.
The backward returns five arrays: dx, ddt, dA, dD and the dB/dC rows."""
_SEQ = r"f32\[\d+,\d+,\d+,8,128\]"
FORWARD = (r"= (" + _SEQ + r"|\(" + _SEQ + r", f32\[\d+,\d+,\d+,\d+,8,128\]\))"
           r" custom-call\(" + _SEQ + " ")
BACKWARD = (r"= \(" + _SEQ + ", " + _SEQ + r", f32\[\d+,\d+,\d+,8,128\], "
            r"f32\[\d+,\d+,8,128\], f32\[\d+,\d+,1,\d+\]\) custom-call\(")
