"""A residual stream of four lanes round latent attention (a query latent,
yarn) and dropless routed experts, with a multi-token-prediction block
(`models/latent_moe.py` with `hc_mult`): `arith_hc_moe.py`'s counts, the rows
a run routed here by `moe_lib.py`, EVERY kernel found by its NAME in
`op_name` (benchmark/hc_faces.py, through the program's report)."""
from benchmark import arith_hc_moe, arith_moe, cca_lib, hc_faces
from benchmark.cca_lib import named_kernels_share as share  # noqa: F401
from benchmark.families import flops_by_routed_rows, grouped_forward
from benchmark.gdn_lib import rows_a_chip
from benchmark.layer_lib import peak
from benchmark.moe_lib import group_sizes, rows_per_layer  # noqa: F401

KERNELS = {
    "mla_forward": hc_faces.FLASH_FORWARD,
    "grouped_forward": hc_faces.GROUPED_FORWARD,
    "grouped_all": hc_faces.GROUPED_ALL,
    "hc_forward": hc_faces.HC_FORWARD,
    "hc_all": hc_faces.HC_ALL,
}
train_flops_per_token = flops_by_routed_rows(arith_hc_moe, rows_per_layer)


def _mla_forward(trace, counters, cell):
    """Compute-bound: the operations over the causal triangle's pairs
    (`arith_moe.attention_fwd_flops`: 2 x (192 + 128) a visible pair a head)
    over the bf16 peak; the prediction block's call is one more of the
    same."""
    flops = arith_moe.attention_fwd_flops(
        rows_a_chip(counters), counters["model"],
        counters["train"]["sequence_length"])
    return cca_lib.named_kernels_roofline(
        trace, cell, KERNELS["mla_forward"],
        flops / peak(counters, "bf16_flops_per_s"))


def _hc_forward(trace, counters, cell):
    """Memory-bound: the two forward kernels' calls, each at its least
    bytes over the HBM peak (`arith_hc_moe.hc_pre_fwd_min_bytes`,
    `hc_post_fwd_min_bytes`: the stream once in, and once out where it is
    written), over both kernels' device time."""
    tokens = rows_a_chip(counters) * counters["train"]["sequence_length"]
    least = zip(hc_faces.HC_FORWARD, (
        arith_hc_moe.hc_pre_fwd_min_bytes(tokens, counters["model"]),
        arith_hc_moe.hc_post_fwd_min_bytes(tokens, counters["model"])))
    least_s = spent_s = 0.0
    for pattern, least_bytes in least:
        found = cca_lib.named_operations(trace, cell, pattern,
                                         kernels_only=True)
        if found is None:
            return None
        least_s += found["calls"] * least_bytes / peak(counters,
                                                      "hbm_bytes_per_s")
        spent_s += found["ms"] * 1e-3
    return 100.0 * least_s / spent_s if spent_s > 0 else None


ROOFLINES = {
    "mla_forward": _mla_forward,
    "grouped_forward": grouped_forward(hc_faces.GROUPED_FORWARD, group_sizes,
                                       by_name=True),
    "hc_forward": _hc_forward,
}
