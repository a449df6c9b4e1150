"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick for every share of a peak the benchmark reports:
the least work a call must do, never what an implementation happens to
do (padding rows, padded expert widths and re-read weight tiles are
waste, and count against the share).
"""
from __future__ import annotations

BF16 = 2


def cmoe_dims(dm: dict, cmoe: dict) -> dict:
    """Expert width and counts of an SxAyEz conversion."""
    n = int(cmoe["num_experts"])
    return {"m": dm["dff"] // n, "shared": int(cmoe["num_shared"]),
            "routed": n - int(cmoe["num_shared"]), "k": int(cmoe["top_k"])}


def row_flops(dm: dict, cm: dict, ctx: int, head: bool) -> float:
    """Model FLOPs of one token row through every layer: projections,
    attention over ``ctx`` keys, the router, the shared and the k active
    routed experts, and (``head``) the output head."""
    d, hd, H, KH = dm["d"], dm["hd"], dm["H"], dm["KH"]
    per_layer = (2 * d * (H + 2 * KH) * hd + 2 * H * hd * d
                 + 4 * H * hd * ctx
                 + 4 * d * cm["routed"]
                 + 6 * d * cm["m"] * (cm["shared"] + cm["k"]))
    return dm["L"] * per_layer + (2 * d * dm["V"] if head else 0)


def moe_gmm_needs(dm: dict, cm: dict, live_rows: int) -> tuple[float, float]:
    """(FLOPs, bytes) one ``moe_gmm_ragged`` call (one layer) needs for
    ``live_rows`` tokens: gate, up and down products for each of their k
    routed assignments; the weights of the experts they reach (min(routed
    experts, assignments), each read once) and the rows in and out."""
    a = live_rows * cm["k"]
    d, m = dm["d"], cm["m"]
    flops = 6.0 * d * m * a
    experts = min(cm["routed"], a)
    nbytes = BF16 * (3.0 * d * m * experts + 2.0 * a * d)
    return flops, nbytes


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Roofline: the larger of compute time and memory time, and which
    bound it is."""
    tc = flops / pk["bf16_flops_per_s"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
