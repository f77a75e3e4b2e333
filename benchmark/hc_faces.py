"""How the hyper-connected latent-attention model's kernels are found: data
that families/xing4_hc_mla_moe.py binds.  Every call is found by the kernel's
own name, which `pl.pallas_call(name=)` leaves in the instruction's `op_name`
(ops/hyper_connection.py, ops/attention.py, ops/grouped_matmul.py) and the
program's report carries for every instruction a trace can show
(`programs["train.step"]` in timeline.json; benchmark/part_lib.py joins it to
the trace), as benchmark/ssd_faces.py does: the patterns below are searched in
`op_name`, among the step module's Mosaic custom calls.  A name does not move
with the kernel's operands.  The forwards run in the forward pass and again
under remat; each is a call of that name.  `hc_pre_fwd` is also the collapse
behind the stack (and behind the prediction block).  A program without the
kernels (an earlier commit) shows no such call and the readers give
nothing."""
from benchmark.cca_faces import GROUPED_ALL, GROUPED_FORWARD  # noqa: F401
from benchmark.ssd_faces import FLASH_FORWARD  # noqa: F401

HC_PRE_FORWARD = r"/hc_pre_fwd(?:/|$)"
HC_POST_FORWARD = r"/hc_post_fwd(?:/|$)"
HC_PRE_BACKWARD = r"/hc_pre_bwd(?:/|$)"
HC_POST_BACKWARD = r"/hc_post_bwd(?:/|$)"
HC_FORWARD = (HC_PRE_FORWARD, HC_POST_FORWARD)
# what `hc_share` sums
HC_ALL = (*HC_FORWARD, HC_PRE_BACKWARD, HC_POST_BACKWARD)
