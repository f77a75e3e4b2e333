"""How the `.ssd` readers find the kernels of their cell: data that the
readers under layer_metrics/ share.  The calls are found by the kernel's own
name, which `pl.pallas_call(name=)` leaves in the instruction's `op_name`
(ops/ssd_scan.py, ops/grouped_matmul.py) and the program's report carries for
every instruction a trace can show (`programs["train.step"]` in
timeline.json; benchmark/part_lib.py joins it to the trace), as
benchmark/cca_faces.py does for the square experts: the patterns below are
searched in `op_name`, among the step module's Mosaic custom calls.  A name
does not move with the kernel's operands, so a later kernel PR that changes
what the calls take is still read.  The forwards run in the forward pass and
again under remat; each is a call of that name.  A program without the
kernels (an earlier commit) shows no such call and the readers give
nothing."""
from benchmark.cca_faces import GROUPED_FORWARD  # noqa: F401

SSD_FORWARD = r"/ssd_scan_fwd(?:/|$)"
SSD_BACKWARD = r"/ssd_scan_bwd(?:/|$)"
